"""Parameter / activation / cache sharding rules (DESIGN.md Sec. 6).

Scheme (baseline = megatron-style TP + hierarchical DP):

  * batch over ("pod", "data"); gradients all-reduce ICI-then-DCN (XLA
    derives the hierarchy from mesh axis order).
  * TP over "model": attention heads + FFN hidden + vocab; the residual
    stream stays replicated over "model" (activation all-reduce after attn
    and FFN, the classic schedule).  ``activation_mode="sp"`` switches the
    residual stream to sequence-sharding over "model" between blocks
    (sequence parallelism -- a hillclimb lever, not the baseline).
  * MoE experts over "model" (replicated-activation EP: the combine is the
    same all-reduce dense TP pays; no all-to-all).
  * KV caches sequence-sharded over "model" (GQA kv_heads < 16 forbids head
    sharding); GSPMD's partial-softmax handling of the sharded seq axis is
    exactly flash-decoding.
  * ZeRO-1: optimizer moments additionally sharded over "data" on their
    first divisible replicated dim.

Rules match parameter KEYPATHS (stable, test-pinned), not shapes.
"""
from __future__ import annotations

import re
from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any

# (keypath regex, PartitionSpec builder) -- first match wins.
# Keypaths look like: ['units']['slot0']['attn']['wq']['kernel']
_RULES: Tuple[Tuple[str, P], ...] = (
    # embeddings / lm head: vocab over model
    (r"\['embed'\]\['table'\]$", P("model", None)),
    (r"\['lm_head'\]\['kernel'\]$", P(None, "model")),
    # attention projections
    (r"\['(wq|wk|wv)'\]\['kernel'\]$", P(None, "model")),
    (r"\['(wq|wk|wv)'\]\['bias'\]$", P("model")),
    (r"\['wo'\]\['kernel'\]$", P("model", None)),
    (r"\['wo'\]\['bias'\]$", P()),
    # MoE: experts over model (EP) + FSDP over data on the d_ff dim --
    # without the data shard, 100B+ of expert weights replicate per
    # data-rank (llama4: 13.6 GiB/dev, over budget).  GSPMD all-gathers the
    # f-shards per layer at use (the standard FSDP trade).
    (r"\['router'\]", P()),
    (r"\['experts'\]\['(gate|up)'\]$", P("model", None, "data")),
    (r"\['experts'\]\['down'\]$", P("model", "data", None)),
    (r"\['experts'\]\[.*\]\['(w_b|t)'\]$", P("model", None, None)),
    # FFN / GLU
    (r"\['ffn'\]\['(gate|up)'\]\['kernel'\]$", P(None, "model")),
    (r"\['ffn'\]\['(gate|up)'\]\['bias'\]$", P("model")),
    (r"\['ffn'\]\['down'\]\['kernel'\]$", P("model", None)),
    (r"\['ffn'\]\['down'\]\['bias'\]$", P()),
    # KAN-FFN: up shards n_out, down shards n_in (t is (n_in, nb, n_out))
    (r"\['kan_up'\]\['w_b'\]$", P(None, "model")),
    (r"\['kan_up'\]\['t'\]$", P(None, None, "model")),
    (r"\['kan_down'\]\['w_b'\]$", P("model", None)),
    (r"\['kan_down'\]\['t'\]$", P("model", None, None)),
    # xLSTM / RG-LRU inner projections: shard the inner width
    (r"\['(up|in_x|in_gate|wx|wif|wa)'\]\['kernel'\]$", P(None, "model")),
    (r"\['(up|in_x|in_gate|wx|wif|wa)'\]\['bias'\]$", P("model")),
    (r"\['(down|out)'\]\['kernel'\]$", P("model", None)),
    (r"\['(down|out)'\]\['bias'\]$", P()),
    (r"\['conv'\]$", P(None, "model")),
    (r"\['lambda'\]$", P("model")),
    (r"\['r'\]$", P()),                       # sLSTM recurrent (small)
    (r"\['frontend_proj'\]\['kernel'\]$", P(None, "model")),
    # norms and anything else small: replicated
    (r".*", P()),
)


def _spec_for_path(path_str: str, ndim: int, shape, mesh) -> P:
    for pat, spec in _RULES:
        if re.search(pat, path_str):
            return _fit(spec, ndim, shape, mesh, path_str)
    return P()


def _fit(spec: P, ndim: int, shape, mesh, path_str: str) -> P:
    """Adjust a rule spec to the actual array rank (stacked layer dim!) and
    drop sharding on axes not divisible by the mesh axis size."""
    parts = list(spec)
    # stacked-under-scan params have a leading (n_units,) axis
    while len(parts) < ndim:
        parts.insert(0, None)
    parts = parts[:ndim]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for dim, ax in zip(shape, parts):
        if ax is not None and dim % sizes.get(ax, 1) != 0:
            ax = None                    # not divisible -> replicate
        out.append(ax)
    return P(*out)


def param_shardings(params: PyTree, mesh, fsdp: bool = False) -> PyTree:
    """NamedSharding pytree for a parameter pytree (works on shapes too).

    ``fsdp=True`` additionally shards every large tensor over 'data' on its
    first divisible replicated dim (ZeRO-3-style fully sharded params).
    GSPMD all-gathers weights at use, per scanned layer -- the standard
    memory<->collective trade that big archs (10B+) need to fit 16 GB/chip.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dsize = sizes.get("data", 1)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        ps = jax.tree_util.keystr(path)
        spec = _spec_for_path(ps, len(leaf.shape), leaf.shape, mesh)
        if fsdp and int(np.prod(leaf.shape)) > 2 ** 20:
            parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
            if "data" not in parts:
                for i, (dim, ax) in enumerate(zip(leaf.shape, parts)):
                    if ax is None and dim % dsize == 0 and dim >= dsize:
                        parts[i] = "data"
                        break
                spec = P(*parts)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def zero1_shardings(opt_moments: PyTree, base: PyTree, mesh) -> PyTree:
    """ZeRO-1: extend each moment's param sharding with 'data' on the first
    still-replicated divisible dim."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dsize = sizes.get("data", 1)

    def one(leaf, sh):
        spec = list(sh.spec) + [None] * (len(leaf.shape) - len(sh.spec))
        if "data" in spec:            # already data-sharded (FSDP params)
            return NamedSharding(mesh, P(*spec))
        for i, (dim, ax) in enumerate(zip(leaf.shape, spec)):
            if ax is None and dim % dsize == 0 and dim >= dsize:
                spec[i] = "data"
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, opt_moments, base)


# ---------------------------------------------------------------------------
# Batch / activation / cache shardings
# ---------------------------------------------------------------------------

def dp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _dp_size(mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(np.prod([sizes[a] for a in dp_axes(mesh)] or [1]))


def batch_shardings(batch: PyTree, mesh) -> PyTree:
    """tokens/(frames|patches): batch dim over (pod, data), rest replicated."""
    axes = dp_axes(mesh)
    total = _dp_size(mesh)

    def one(leaf):
        if leaf.shape and axes and leaf.shape[0] % total == 0:
            return NamedSharding(
                mesh, P(axes, *([None] * (len(leaf.shape) - 1))))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, batch)


def cache_shardings(caches: PyTree, mesh, seq_axis_min: int = 1024) -> PyTree:
    """KV caches: batch over (pod,data) + sequence over model when long.
    Recurrent states / mLSTM matrix memory: batch over (pod,data) only."""
    axes = dp_axes(mesh)
    total = _dp_size(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msize = sizes.get("model", 1)

    def one(path, leaf):
        ps = jax.tree_util.keystr(path)
        spec = [None] * len(leaf.shape)
        if not leaf.shape:
            return NamedSharding(mesh, P())
        # stacked-under-scan caches (under ['units']) carry a leading
        # (n_units,) axis -- the batch dim is right after it, NEVER dim 0
        # (48 units happens to divide 16 data ranks and must not be
        # mistaken for batch, or the cache replicates over 'model').
        batch_dim = 1 if "['units']" in ps else 0
        if (batch_dim < len(leaf.shape) and axes
                and leaf.shape[batch_dim] % total == 0
                and leaf.shape[batch_dim] >= total):
            spec[batch_dim] = axes
        else:
            batch_dim = -1
        if (re.search(r"\['(k|v|ck|cv|k_scale|v_scale)'\]$", ps)
                and len(leaf.shape) >= 3):
            seq_dim = batch_dim + 1 if batch_dim >= 0 else None
            if (seq_dim is not None
                    and leaf.shape[seq_dim] >= seq_axis_min
                    and leaf.shape[seq_dim] % msize == 0):
                spec[seq_dim] = "model"      # sequence-sharded KV
        return NamedSharding(mesh, P(*spec))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches)
    return jax.tree_util.tree_unflatten(
        treedef, [one(p, l) for p, l in flat])


def replicated(mesh):
    return NamedSharding(mesh, P())
