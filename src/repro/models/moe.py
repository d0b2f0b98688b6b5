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
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.kan import KANConfig, kan_init
from repro.core.splines import SplineSpec
from repro.kernels.kan_fused.ops import flatten_t, kan_linear
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
    ffn_kind: str = "swiglu"     # swiglu | kan
    kan_grid: int = 4
    kan_order: int = 3

    def capacity(self, n_tokens: int) -> int:
        c = int(self.capacity_factor * self.top_k * n_tokens
                / self.n_experts) + 1
        return max(self.top_k, min(c, n_tokens))


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
    """x: (B, S, d) -> {"out": (B, S, d), "aux_loss": scalar}.

    With an ambient mesh (jax.set_mesh) and swiglu experts, runs the
    shard_map EP path; otherwise the identical-math local path (tests, 1
    device).
    """
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
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
        from repro.models.ffn import FFNConfig, ffn_apply
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


def moe_init_with_shared(key, cfg: MoEConfig, dtype=jnp.float32) -> Dict:
    from repro.models.ffn import FFNConfig, ffn_init
    k1, k2 = jax.random.split(key)
    p = moe_init(k1, cfg, dtype)
    if cfg.shared_expert:
        p["shared"] = ffn_init(
            k2, FFNConfig(cfg.d_model, cfg.d_ff, kind="swiglu"), dtype)
    return p
