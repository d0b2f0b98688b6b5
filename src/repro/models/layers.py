"""Shared neural building blocks (functional, pytree params)."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DTypePolicy:
    """Mixed-precision policy: bf16 compute / fp32 reductions on TPU."""

    param: jnp.dtype = jnp.float32
    compute: jnp.dtype = jnp.float32
    accum: jnp.dtype = jnp.float32

    @classmethod
    def bf16(cls):
        return cls(param=jnp.bfloat16, compute=jnp.bfloat16,
                   accum=jnp.float32)


F32 = DTypePolicy()
BF16 = DTypePolicy.bf16()


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6, offset: float = 0.0):
    """RMSNorm in fp32 (gemma-style optional +1 offset via ``offset=1``)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (params["scale"].astype(jnp.float32) + offset)).astype(x.dtype)


def layernorm_init(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def layernorm(params, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(
        jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / embedding
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    p = {"kernel": (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def dense(params, x, accum=jnp.float32):
    y = jnp.dot(x, params["kernel"], preferred_element_type=accum)
    if "bias" in params:
        y = y + params["bias"].astype(accum)
    return y.astype(x.dtype)


def embed_init(key, vocab: int, dim: int, dtype=jnp.float32):
    return {"table": (jax.random.normal(key, (vocab, dim)) * 0.02).astype(dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x, accum=jnp.float32):
    """Tied LM head: logits = x @ table^T."""
    return jnp.dot(x, params["table"].T, preferred_element_type=accum)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, base: float = 10000.0) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (base ** exponent)                    # (head_dim/2,)


def apply_rope(x: jax.Array, positions: jax.Array,
               base: float = 10000.0) -> jax.Array:
    """x: (..., S, H, head_dim); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, base)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (...,S,hd/2)
    angles = angles[..., None, :]                                # head axis
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Sharding hints (mesh-agnostic: axes not in the current mesh are dropped)
# ---------------------------------------------------------------------------

def shard_hint(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint that degrades gracefully on any mesh.

    ``axes`` entries are axis names, tuples of names, or None (one per dim,
    trailing dims default to None).  Names absent from the active mesh are
    dropped, so model code can state its intent ('experts over model,
    capacity over data') and still run on a 1-device CPU mesh.
    """
    mesh = jax.sharding.get_abstract_mesh()
    names = set(mesh.axis_names)
    if not names:
        return x

    def keep(a):
        if a is None:
            return None
        if isinstance(a, tuple):
            kept = tuple(n for n in a if n in names)
            return kept if kept else None
        return a if a in names else None

    spec = [keep(a) for a in axes]
    spec += [None] * (x.ndim - len(spec))
    # drop shards that don't divide the dim
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    out = []
    for dim, a in zip(x.shape, spec):
        n = 1
        for nm in (a if isinstance(a, tuple) else (a,) if a else ()):
            n *= sizes.get(nm, 1)
        out.append(a if n > 1 and dim % n == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*out))


# ---------------------------------------------------------------------------
# Misc
# ---------------------------------------------------------------------------

def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    """Gemma-2 style logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


ACT_FNS = {
    "relu": jax.nn.relu,
    "gelu": gelu,
    "silu": jax.nn.silu,
}


def count_params(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
