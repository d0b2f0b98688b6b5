"""Weights made from the seed, on the device, in one jitted call.

Every leaf is uniform noise of a stated standard deviation (plus a mean),
from a counter-based hash of (seed, leaf number, element index): cheap to
compile for hundreds of leaves, the same on every platform, and any
seed up to 64 bits gives its own weights.
"""
from __future__ import annotations

import math
from typing import Callable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# (shape, dtype, mean, std) of one leaf
LeafSpec = Tuple[Tuple[int, ...], object, float, float]


def _mix(x: jax.Array) -> jax.Array:
    """The murmur3 finalizer: a bijection on uint32 that scrambles bits."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _uniform(shape, seed_lo, seed_hi, leaf: int) -> jax.Array:
    """Uniform on [-1, 1) of ``shape``."""
    n = int(np.prod(shape)) if shape else 1
    i = jax.lax.iota(jnp.uint32, n)
    h = _mix(i * jnp.uint32(0x9E3779B1) + seed_lo
             + jnp.uint32((leaf * 0x632BE5AB) & 0xFFFFFFFF))
    h = _mix(h ^ seed_hi)
    u = (h >> 8).astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0
    return u.reshape(shape)


def make(specs: Sequence[LeafSpec], seed: int) -> List[jax.Array]:
    """One array per spec, made together in one jitted call."""
    specs = [(tuple(s), d, float(m), float(sd)) for s, d, m, sd in specs]

    def build(lo, hi):
        out = []
        for k, (shape, dtype, mean, std) in enumerate(specs):
            u = _uniform(shape, lo, hi, k)
            out.append((mean + std * math.sqrt(3.0) * u).astype(dtype))
        return out

    seed = int(seed) & (2**64 - 1)
    lo = jnp.uint32(seed & 0xFFFFFFFF)
    hi = jnp.uint32((seed >> 32) ^ 0x5BD1E995)
    return jax.jit(build)(lo, hi)


def make_tree(template, leaf_spec: Callable[[str, tuple], Tuple[float,
                                                                 float]],
              dtype, seed: int):
    """Fill the structure of ``template`` (arrays or shape structs): the
    leaf at path ``p`` with shape ``s`` gets ``leaf_spec(p, s)`` = (mean,
    std), in ``dtype``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    specs = []
    for path, leaf in leaves:
        key = "/".join(_name(k) for k in path)
        mean, std = leaf_spec(key, tuple(leaf.shape))
        specs.append((leaf.shape, dtype, mean, std))
    return jax.tree_util.tree_unflatten(treedef, make(specs, seed))


def _name(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)
