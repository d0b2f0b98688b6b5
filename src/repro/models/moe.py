"""Mixture-of-Experts with capacity-bounded sort-based dispatch.

Expert parallelism (DESIGN.md Sec. 6): experts shard over the ``model`` mesh
axis; token activations stay sharded over ``data`` and replicated over
``model``.  Dispatch builds an (E, C, d) buffer -- sharded over E -- so each
model-rank materializes only its local experts' slots; the per-token combine
is a sum over experts that GSPMD lowers to the same all-reduce the dense TP
path already pays.  No all-to-all on the critical path.

Dispatch is sort-free one-hot-free at the FLOP level that matters: position-
in-expert ranks come from a cumsum over the (tokens, E) assignment matrix --
O(T*E) bookkeeping vs O(T*E*d) compute, negligible for d >= 1024.  Tokens
beyond capacity C = ceil(T/E * k * capacity_factor) are dropped (their
combine weight is 0), the standard capacity contract.

The per-expert FFN is SwiGLU (qwen3/llama4 style); ``shared_expert`` adds the
always-on dense expert of llama4-scout.  With ``ffn_kind="kan"`` each expert
becomes a KAN stack -- the paper's technique applied inside MoE experts
(DESIGN.md Sec. 5).

``ffn_kind="kanffn"`` is the served expert layer (``moe_kanffn``): each
expert is the KAN-FFN of models/ffn.kan_ffn_apply (a KAN up-projection,
then a linear down-projection with bias), and the layer is DROPLESS: a
token's output never depends on which other tokens share its batch, so a
prefill and a decode step compute the same function.  The layer holds a
share of the experts (``first_expert``, ``n_held``: expert parallelism's
share of one chip), routes over all ``n_experts``, and computes only the
assignments to the experts it holds; the rest add nothing here.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.kan import KANConfig, kan_fused_weights, kan_init
from repro.core.splines import SplineSpec
from repro.kernels.kan_fused.grouped import kan_grouped, tile_rows
from repro.kernels.kan_fused.ops import flatten_t, kan_linear
from repro.models.ffn import FFNConfig, ffn_apply, ffn_init
from repro.models.layers import dense, dense_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int                    # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    shared_expert: bool = False  # llama4: one always-on shared expert
    router_jitter: float = 0.0
    ffn_kind: str = "swiglu"     # swiglu | kan | kanffn
    kan_grid: int = 4
    kan_order: int = 3
    # ffn_kind "kanffn": the share held here (experts first_expert ..
    # first_expert + n_held - 1; None: all) and the grouped kernel's
    # dispatch
    first_expert: int = 0
    n_held: Optional[int] = None
    impl: str = "auto"

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held

    def expert_ffn_cfg(self) -> FFNConfig:
        """One KAN-FFN expert (ffn_kind "kanffn"), its hidden width the
        parameter-matched ``FFNConfig.kanffn_hidden``."""
        return FFNConfig(self.d_model, self.d_ff, kind="kanffn",
                         kan_grid=self.kan_grid, kan_order=self.kan_order)

    def capacity(self, n_tokens: int) -> int:
        c = int(self.capacity_factor * self.top_k * n_tokens
                / self.n_experts) + 1
        return max(self.top_k, min(c, n_tokens))


def stored_hidden(h: int) -> int:
    """Length the KAN-FFN experts' hidden axis is stored at: ``h`` rounded
    up to 8 rows.  XLA lays out a TPU array whose second-minor axis is not
    a whole number of 8-row tiles column-major, and a kernel that reads
    it row-major would then get a copy of every expert's weights on each
    call."""
    return -(-h // 8) * 8


def moe_init(key, cfg: MoEConfig, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 6)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {"router": dense_init(ks[0], d, E, dtype=dtype)}
    if cfg.ffn_kind == "swiglu":
        init = jax.nn.initializers.normal(stddev=d ** -0.5)
        p["experts"] = {
            "gate": init(ks[1], (E, d, f), dtype),
            "up": init(ks[2], (E, d, f), dtype),
            "down": init(ks[3], (E, f, d), dtype),
        }
    elif cfg.ffn_kind == "kan":
        spec = SplineSpec(cfg.kan_grid, cfg.kan_order)
        h = max(8, f // (spec.n_bases + 1))
        up_cfg = KANConfig(d, h, spec)
        down_cfg = KANConfig(h, d, spec)
        ek = jax.random.split(ks[1], E)
        ups = [kan_init(k_, up_cfg, dtype) for k_ in ek]
        ek2 = jax.random.split(ks[2], E)
        downs = [kan_init(k_, down_cfg, dtype) for k_ in ek2]
        p["experts"] = {
            "up": jax.tree.map(lambda *a: jnp.stack(a), *ups),
            "down": jax.tree.map(lambda *a: jnp.stack(a), *downs),
        }
    elif cfg.ffn_kind == "kanffn":
        # the held experts only, their KAN up-projections stored in the
        # fused [w_b ; t] layout the grouped kernel reads (ops.fuse_wt's,
        # transposed: (h, d_model * (G+K+1))); the hidden axis is stored
        # ``stored_hidden`` long, its extra rows zero and never used
        fcfg = cfg.expert_ffn_cfg()
        up_cfg = fcfg.kanffn_up_cfg()
        pad = stored_hidden(fcfg.kanffn_hidden) - fcfg.kanffn_hidden

        def one(k):
            e = ffn_init(k, fcfg, dtype)
            wt = kan_fused_weights(e["kan_up"], up_cfg).T
            return {"wt": jnp.pad(wt, ((0, pad), (0, 0))),
                    "w": jnp.pad(e["w"], ((0, pad), (0, 0))), "b": e["b"]}

        p["experts"] = jax.vmap(one)(jax.random.split(ks[1], cfg.held))
    else:
        raise ValueError(cfg.ffn_kind)
    return p


def _expert_ffn(params: Dict, h: jax.Array, cfg: MoEConfig) -> jax.Array:
    """h: (E, C, d) -> (E, C, d), vectorized over experts."""
    if cfg.ffn_kind == "swiglu":
        e = params["experts"]
        g = jnp.einsum("ecd,edf->ecf", h, e["gate"],
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("ecd,edf->ecf", h, e["up"],
                       preferred_element_type=jnp.float32)
        z = (jax.nn.silu(g) * u).astype(h.dtype)
        return jnp.einsum("ecf,efd->ecd", z, e["down"],
                          preferred_element_type=jnp.float32).astype(h.dtype)
    # KAN experts: vmap the fused KAN layer over the expert axis.
    spec = SplineSpec(cfg.kan_grid, cfg.kan_order)

    def one(hp, up, down):
        mid = kan_linear(hp, up["w_b"], flatten_t(up["t"]), spec, impl="jnp")
        return kan_linear(mid, down["w_b"], flatten_t(down["t"]), spec,
                          impl="jnp")

    return jax.vmap(one)(h, params["experts"]["up"],
                         params["experts"]["down"])


def _moe_local(xt, router_k, gate_w, up_w, down_w, cfg: MoEConfig,
               e0, E_loc: int, model_axis: Optional[str]) -> Dict:
    """Token routing + expert FFN + combine over E_loc LOCAL experts.

    Runs either as the whole computation (1 device / no mesh: E_loc = E,
    e0 = 0) or as one model-rank's slice inside shard_map (replicated-
    activation expert parallelism): every rank sees the same tokens,
    selects only its local experts' assignments, computes them, and the
    per-token combine is the psum over 'model' that dense TP already pays.
    All dispatch tensors are LOCAL: (E_loc, C, d) with T_loc tokens -- the
    giant global scatter that pure GSPMD materializes never exists.
    """
    T, d = xt.shape
    E, K = cfg.n_experts, cfg.top_k

    logits = jnp.dot(xt, router_k,
                     preferred_element_type=jnp.float32)        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                      # (T, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # capacity per LOCAL expert, padded to keep shapes friendly
    C = -(-int(cfg.capacity_factor * T * K) // E)   # ceil
    C = max(8, -(-C // 8) * 8)

    le = top_e - e0                                             # local ids
    in_range = (le >= 0) & (le < E_loc)
    le_c = jnp.clip(le, 0, E_loc - 1)
    onehot = jax.nn.one_hot(le_c, E_loc, dtype=jnp.int32) \
        * in_range[..., None].astype(jnp.int32)                 # (T, K, E_loc)
    flat = onehot.reshape(T * K, E_loc)
    rank = jnp.cumsum(flat, axis=0) - flat                      # exclusive
    pos = jnp.sum(rank * flat, axis=-1).reshape(T, K)
    keep = in_range & (pos < C)
    gate = jnp.where(keep, top_p, 0.0)

    flat_e = le_c.reshape(-1)
    flat_pos = jnp.where(keep.reshape(-1), pos.reshape(-1), C)  # C = trash
    buf = jnp.zeros((E_loc, C + 1, d), xt.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), K)
    buf = buf.at[flat_e, flat_pos].add(xt[tok_idx])[:, :C]

    g = jnp.einsum("ecd,edf->ecf", buf, gate_w,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", buf, up_w,
                   preferred_element_type=jnp.float32)
    z = (jax.nn.silu(g) * u).astype(xt.dtype)
    hidden = jnp.einsum("ecf,efd->ecd", z, down_w,
                        preferred_element_type=jnp.float32).astype(xt.dtype)

    padded = jnp.concatenate(
        [hidden, jnp.zeros((E_loc, 1, d), hidden.dtype)], axis=1)
    picked = padded[flat_e, flat_pos].reshape(T, K, d)
    out = jnp.sum(picked * gate[..., None].astype(picked.dtype), axis=1)

    # load-balancing aux (Switch-style), over the full router distribution
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)

    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)     # combine across expert ranks
    return out, aux


def _ambient_mesh_axes():
    mesh = jax.sharding.get_abstract_mesh()
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def moe_apply(params: Dict, x: jax.Array, cfg: MoEConfig,
              rng: Optional[jax.Array] = None) -> Dict:
    """x: (B, S, d) -> {"out": (B, S, d), "aux_loss": scalar}, and for
    KAN-FFN experts ``"counts"`` (``moe_kanffn``).

    With an ambient mesh (jax.set_mesh) and swiglu experts, runs the
    shard_map EP path; otherwise the identical-math local path (tests, 1
    device).
    """
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    if cfg.ffn_kind == "kanffn":
        out, aux, counts = moe_kanffn(params, xt, cfg)
        return {"out": out.reshape(B, S, d), "aux_loss": aux,
                "counts": counts}
    axes = _ambient_mesh_axes()
    e = params["experts"]

    if "model" in axes and cfg.ffn_kind == "swiglu":
        from jax.sharding import PartitionSpec as P
        E_loc = cfg.n_experts // axes["model"]
        assert E_loc * axes["model"] == cfg.n_experts, \
            (cfg.n_experts, axes["model"])
        dp = tuple(a for a in ("pod", "data") if a in axes)

        def body(xt_l, rk, gw, uw, dw):
            # FSDP: gather the f-shards of the local experts' weights
            if axes.get("data", 1) > 1:
                gw = jax.lax.all_gather(gw, "data", axis=2, tiled=True)
                uw = jax.lax.all_gather(uw, "data", axis=2, tiled=True)
                dw = jax.lax.all_gather(dw, "data", axis=1, tiled=True)
            e0 = jax.lax.axis_index("model") * E_loc
            out, aux = _moe_local(xt_l, rk, gw, uw, dw, cfg, e0, E_loc,
                                  model_axis="model")
            # aux is identical across 'model' (same tokens, same router);
            # average over data shards
            n_dp = 1
            for a in dp:
                aux = jax.lax.psum(aux, a)
                n_dp *= axes[a]
            return out, aux / n_dp

        out, aux = jax.shard_map(
            body,
            in_specs=(P(dp if dp else None, None), P(None, None),
                      P("model", None, "data"), P("model", None, "data"),
                      P("model", "data", None)),
            out_specs=(P(dp if dp else None, None), P()),
            check_vma=False,
        )(xt, params["router"]["kernel"], e["gate"], e["up"], e["down"])
    elif cfg.ffn_kind == "swiglu":
        out, aux = _moe_local(xt, params["router"]["kernel"], e["gate"],
                              e["up"], e["down"], cfg, 0, cfg.n_experts,
                              model_axis=None)
    else:
        # KAN-expert MoE: local/GSPMD path (extension feature; smoke scale)
        out, aux = _moe_local_kan(params, xt, cfg)

    if cfg.shared_expert and "shared" in params:
        sh = ffn_apply(params["shared"],
                       xt, FFNConfig(cfg.d_model, cfg.d_ff, kind="swiglu"))
        out = out + sh

    return {"out": out.reshape(B, S, d).astype(x.dtype), "aux_loss": aux}


def _moe_local_kan(params: Dict, xt: jax.Array, cfg: MoEConfig):
    """KAN experts: dispatch like _moe_local, expert FFN via vmapped KAN."""
    T, d = xt.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = dense(params["router"], xt).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    C = cfg.capacity(T)
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)
    flat = onehot.reshape(T * K, E)
    rank = jnp.cumsum(flat, axis=0) - flat
    pos = jnp.sum(rank * flat, axis=-1).reshape(T, K)
    keep = pos < C
    gate = jnp.where(keep, top_p, 0.0)
    flat_e = top_e.reshape(-1)
    flat_pos = jnp.where(keep.reshape(-1), pos.reshape(-1), C)
    buf = jnp.zeros((E, C + 1, d), xt.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), K)
    buf = buf.at[flat_e, flat_pos].add(xt[tok_idx])[:, :C]
    hidden = _expert_ffn(params, buf, cfg)
    padded = jnp.concatenate([hidden, jnp.zeros((E, 1, d), hidden.dtype)], 1)
    picked = padded[flat_e, flat_pos].reshape(T, K, d)
    out = jnp.sum(picked * gate[..., None].astype(picked.dtype), axis=1)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    return out, E * jnp.sum(me * ce)


def group_rows(expert: jax.Array, mine: jax.Array, n_held: int,
               bm: int, n_tiles: int) -> Dict[str, jax.Array]:
    """The sorted, tile-padded row layout of one layer's assignments.

    ``expert`` (A,) is each assignment's expert among the ``n_held`` held
    here and ``mine`` (A,) whether it is held here at all.  Every held
    expert's assignments take consecutive rows, in assignment order, from
    the start of its group; groups follow in expert order, each padded to
    a multiple of ``bm`` rows, in ``n_tiles * bm`` rows in all.  Returns
    ``dest`` (A,): each assignment's row, ``n_tiles * bm`` where it is not
    held here; ``sizes`` (n_held,): the rows of each group; ``padded``:
    the same, padded to the tile; ``tile_expert`` (n_tiles,): each tile's
    expert (the last group's past the used tiles); ``n_used``: the tiles
    that hold rows.  Nothing is dropped: ``n_tiles`` must bound the tiles
    any routing can need."""
    onehot = jax.nn.one_hot(jnp.where(mine, expert, n_held), n_held,
                            dtype=jnp.int32)                  # (A, n_held)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    sizes = jnp.sum(onehot, axis=0)
    padded = -(-sizes // bm) * bm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    rows = n_tiles * bm
    dest = jnp.where(mine, starts[jnp.clip(expert, 0, n_held - 1)] + rank,
                     rows)
    tile_start = jnp.arange(n_tiles, dtype=jnp.int32) * bm
    tile_expert = jnp.minimum(
        jnp.sum(ends[None, :] <= tile_start[:, None], axis=-1), n_held - 1)
    return {"dest": dest, "sizes": sizes, "padded": padded,
            "tile_expert": tile_expert.astype(jnp.int32),
            "n_used": ends[-1] // bm}


def moe_kanffn(params: Dict, xt: jax.Array, cfg: MoEConfig):
    """The served KAN-FFN expert layer on (T, d) tokens: dropless, over
    the experts held here.  Returns (out (T, d), aux_loss, counts).

    The router is a bias-free linear layer to all ``n_experts`` logits,
    accumulated in float32; softmax in float32; top ``top_k``; the k
    weights renormalised to sum to 1.  Assignments to experts held here
    are laid out by ``group_rows`` and run through the grouped KAN
    up-projection (kernels/kan_fused/grouped.py) and a grouped linear
    down-projection (``jax.lax.ragged_dot``); each token sums its experts'
    outputs (with their biases) under its weights.  ``counts`` holds this
    layer's int32 ``routed`` (rows sent to experts held here), ``active``
    (held experts with a row) and ``max_group`` (the largest group)."""
    T, d = xt.shape
    E, K, held = cfg.n_experts, cfg.top_k, cfg.held
    logits = jnp.dot(xt, params["router"]["kernel"],
                     preferred_element_type=jnp.float32)     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                   # (T, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    local = top_e - cfg.first_expert
    mine = (local >= 0) & (local < held)

    # a token picks an expert once: a group holds at most T rows
    bm = tile_rows(T * K / E, xt.dtype)
    n_tiles = min(held * -(-T // bm), -(-T * min(K, held) // bm) + held)
    g = group_rows(local.reshape(-1), mine.reshape(-1), held, bm, n_tiles)
    rows = n_tiles * bm
    # row r holds token src[r]; a padding row reads T, out of range: 0
    src = jnp.full((rows + 1,), T, jnp.int32).at[g["dest"]].set(
        jnp.repeat(jnp.arange(T, dtype=jnp.int32), K))[:rows]
    xs = jnp.take(xt, src, axis=0, mode="fill", fill_value=0)
    ex = params["experts"]
    spec = SplineSpec(cfg.kan_grid, cfg.kan_order)
    hid = kan_grouped(xs, ex["wt"], g["tile_expert"], g["n_used"], spec,
                      bm=bm, impl=cfg.impl)             # (rows, stored h)
    h = cfg.expert_ffn_cfg().kanffn_hidden
    hid = jnp.where(jnp.arange(hid.shape[1]) < h, hid, 0)     # the storage
    y = jax.lax.ragged_dot(hid, ex["w"], g["padded"],         # rows add 0
                           preferred_element_type=jnp.float32)
    gate = jnp.where(mine, top_p, 0.0)                       # (T, K)
    # an assignment not held here reads row ``rows``, out of range: 0
    picked = jnp.take(y, g["dest"].reshape(T, K), axis=0, mode="fill",
                      fill_value=0)                          # (T, K, d)
    # the biases: each token's gate weights per held expert, times b
    per_expert = jnp.sum(jax.nn.one_hot(jnp.where(mine, local, held), held,
                                        dtype=jnp.float32)
                         * gate[..., None], axis=1)          # (T, held)
    out = jnp.sum(gate[..., None] * picked, axis=1) + jnp.dot(
        per_expert, ex["b"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    sizes = g["sizes"]
    counts = {"routed": jnp.sum(sizes), "active": jnp.sum(sizes > 0),
              "max_group": jnp.max(sizes)}
    counts = {k: v.astype(jnp.int32) for k, v in counts.items()}
    return out.astype(xt.dtype), E * jnp.sum(me * ce), counts


def total_counts(per_layer: list) -> Dict[str, jax.Array]:
    """One program's counts over its layers' ``moe_kanffn`` counts:
    ``routed`` and ``active`` summed, ``max_group`` the largest; empty for
    a program without such a layer."""
    if not per_layer:
        return {}
    stack = {k: jnp.stack([c[k] for c in per_layer]) for k in per_layer[0]}
    return {"routed": jnp.sum(stack["routed"]),
            "active": jnp.sum(stack["active"]),
            "max_group": jnp.max(stack["max_group"])}


def moe_init_with_shared(key, cfg: MoEConfig, dtype=jnp.float32) -> Dict:
    k1, k2 = jax.random.split(key)
    p = moe_init(k1, cfg, dtype)
    if cfg.shared_expert:
        p["shared"] = ffn_init(
            k2, FFNConfig(cfg.d_model, cfg.d_ff, kind="swiglu"), dtype)
    return p
