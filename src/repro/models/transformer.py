"""Unified model assembly for all assigned architectures.

One declarative ArchConfig drives everything:

  * block kinds: 'attn' (GQA + FFN/MoE), 'rec' (RG-LRU + FFN),
    'mlstm'/'slstm' (xLSTM, self-contained); ``cfg.pattern`` tiles them.
  * layers are SCANNED: params are stacked per pattern-unit with a leading
    (n_units,) axis and the whole stack compiles as ONE unit body
    (jax.lax.scan), optionally remat'ed -- without this, compiling a
    94-layer MoE for 512 devices is intractable.  Remainder layers
    (n_layers % len(pattern)) run unscanned after the scan.
  * enc_dec adds a bidirectional encoder + cross-attention (whisper);
    prefix_lm + vision frontend makes the prefix-VLM (paligemma);
    frontends are STUBS per the assignment: input_specs provides
    precomputed frame/patch embeddings, a learnable linear adapter maps
    them into the residual stream.
  * losses: chunk-unrolled cross-entropy (never materializes the full
    (B, S, V) logits; unrolled so cost_analysis still sees the FLOPs),
    with z-loss and MoE aux losses.

Everything is functional: params/caches are pytrees, apply fns are pure.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import xlstm as X
from repro.models.layers import (
    dense,
    dense_init,
    embed,
    embed_init,
    layernorm,
    layernorm_init,
    rmsnorm,
    rmsnorm_init,
    shard_hint,
    unembed,
)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Norm helpers (rms vs ln, gemma offset)
# ---------------------------------------------------------------------------

def _norm_init(cfg: ArchConfig, dtype):
    return (rmsnorm_init(cfg.d_model, dtype) if cfg.norm == "rms"
            else layernorm_init(cfg.d_model, dtype))


def _norm(cfg: ArchConfig, p, x):
    if cfg.norm == "rms":
        return rmsnorm(p, x, offset=cfg.norm_offset)
    return layernorm(p, x)


# ---------------------------------------------------------------------------
# Single block: init / train / prefill / decode
# ---------------------------------------------------------------------------

def block_init(key, cfg: ArchConfig, kind: str, *, cross: bool = False,
               causal: bool = True, layer: int = 0) -> Params:
    dtype = cfg.param_dtype
    ks = jax.random.split(key, 8)
    if kind == "attn":
        acfg = cfg.attn_cfg() if causal else cfg.enc_attn_cfg()
        p = {"attn_norm": _norm_init(cfg, dtype),
             "attn": A.attn_init(ks[0], acfg, dtype)}
        if cross:
            p["cross_norm"] = _norm_init(cfg, dtype)
            p["cross"] = A.cross_attn_init(ks[1], cfg.attn_cfg(), dtype)
        fk = cfg.layer_ffn_kind(layer)
        if fk == "moe":
            p["moe_norm"] = _norm_init(cfg, dtype)
            p["moe"] = M.moe_init_with_shared(ks[2], cfg.moe_cfg(), dtype)
        elif fk != "none" and cfg.d_ff > 0:
            p["ffn_norm"] = _norm_init(cfg, dtype)
            p["ffn"] = F.ffn_init(ks[2], cfg.ffn_cfg(layer), dtype)
        return p
    if kind == "rec":
        p = {"rec_norm": _norm_init(cfg, dtype),
             "rec": R.rglru_init(ks[0], cfg.rglru_cfg(), dtype)}
        if cfg.d_ff > 0:
            p["ffn_norm"] = _norm_init(cfg, dtype)
            p["ffn"] = F.ffn_init(ks[1], cfg.ffn_cfg(layer), dtype)
        return p
    if kind == "mlstm":
        return {"mlstm": X.mlstm_init(ks[0], cfg.xlstm_cfg(), dtype)}
    if kind == "slstm":
        return {"slstm": X.slstm_init(ks[0], cfg.xlstm_cfg(), dtype)}
    raise ValueError(kind)


def _apply_ffn_part(p: Params, x, cfg: ArchConfig, layer: int = 0,
                    taps: Optional[Dict[int, jax.Array]] = None,
                    counters: Optional[List] = None):
    """``counters``, where given, collects the layer's MoE counts (the
    KAN-FFN expert layer's, models/moe.moe_kanffn) while it is traced."""
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        r = M.moe_apply(p["moe"], _norm(cfg, p["moe_norm"], x), cfg.moe_cfg())
        x = x + r["out"]
        aux = r["aux_loss"]
        if counters is not None and "counts" in r:
            counters.append(r["counts"])
    elif "ffn" in p:
        xn = _norm(cfg, p["ffn_norm"], x)
        if taps is not None:
            # calibration hook: the normed FFN INPUT of this layer (what
            # the saliency machinery in core/calibrate scores against)
            taps[layer] = xn
        x = x + F.ffn_apply(p["ffn"], xn, cfg.ffn_cfg(layer))
    return x, aux


def block_apply(p: Params, x, cfg: ArchConfig, kind: str, *,
                causal: bool = True,
                prefix_len: Optional[jax.Array] = None,
                memory: Optional[jax.Array] = None,
                layer: int = 0,
                taps: Optional[Dict[int, jax.Array]] = None):
    """Training/encoding path (no cache).  Returns (x, aux_loss)."""
    if kind == "attn":
        acfg = cfg.attn_cfg() if causal else cfg.enc_attn_cfg()
        x = x + A.attention(p["attn"], _norm(cfg, p["attn_norm"], x), acfg,
                            prefix_len=prefix_len)
        if "cross" in p and memory is not None:
            x = x + A.cross_attention(
                p["cross"], _norm(cfg, p["cross_norm"], x), memory,
                cfg.attn_cfg())
        return _apply_ffn_part(p, x, cfg, layer, taps)
    if kind == "rec":
        y, _ = R.rglru_apply(p["rec"], _norm(cfg, p["rec_norm"], x),
                             cfg.rglru_cfg())
        x = x + y
        return _apply_ffn_part(p, x, cfg, layer, taps)
    if kind == "mlstm":
        y, _ = X.mlstm_apply(p["mlstm"], x, cfg.xlstm_cfg())
        return y, jnp.zeros((), jnp.float32)
    if kind == "slstm":
        y, _ = X.slstm_apply(p["slstm"], x, cfg.xlstm_cfg())
        return y, jnp.zeros((), jnp.float32)
    raise ValueError(kind)


def block_init_cache(batch: int, max_len: int, cfg: ArchConfig, kind: str,
                     *, cross_len: int = 0) -> Params:
    dtype = cfg.param_dtype
    if kind == "attn":
        acfg = cfg.attn_cfg()
        size = min(max_len, acfg.window) if acfg.window else max_len
        c = A.init_cache(batch, size, acfg, dtype)
        if cross_len:
            hd = acfg.hd
            c["ck"] = jnp.zeros((batch, cross_len, acfg.n_kv_heads, hd), dtype)
            c["cv"] = jnp.zeros((batch, cross_len, acfg.n_kv_heads, hd), dtype)
        return c
    if kind == "rec":
        return R.rglru_init_state(batch, cfg.rglru_cfg(), dtype)
    if kind == "mlstm":
        return X.mlstm_init_state(batch, cfg.xlstm_cfg(), dtype)
    if kind == "slstm":
        return X.slstm_init_state(batch, cfg.xlstm_cfg())
    raise ValueError(kind)


def block_prefill(p: Params, x, cfg: ArchConfig, kind: str, max_len: int, *,
                  prefix_len=None, memory=None, layer: int = 0,
                  counters: Optional[List] = None):
    """Full-sequence pass that also returns the decode cache."""
    if kind == "attn":
        acfg = cfg.attn_cfg()
        xn = _norm(cfg, p["attn_norm"], x)
        size = min(max_len, acfg.window) if acfg.window else max_len
        # prefill_cache handles the ring layout when S > window
        y, cache = A.prefill_cache(p["attn"], xn, acfg, size,
                                   dtype=cfg.param_dtype)
        x = x + y
        if "cross" in p and memory is not None:
            x = x + A.cross_attention(
                p["cross"], _norm(cfg, p["cross_norm"], x), memory,
                cfg.attn_cfg())
            hd = acfg.hd
            B, Sk, _ = memory.shape
            cache["ck"] = dense(p["cross"]["wk"], memory).reshape(
                B, Sk, acfg.n_kv_heads, hd)
            cache["cv"] = dense(p["cross"]["wv"], memory).reshape(
                B, Sk, acfg.n_kv_heads, hd)
        x, _ = _apply_ffn_part(p, x, cfg, layer, counters=counters)
        return x, cache
    if kind == "rec":
        y, st = R.rglru_apply(p["rec"], _norm(cfg, p["rec_norm"], x),
                              cfg.rglru_cfg())
        x = x + y
        x, _ = _apply_ffn_part(p, x, cfg, layer)
        return x, st
    if kind == "mlstm":
        return X.mlstm_apply(p["mlstm"], x, cfg.xlstm_cfg())
    if kind == "slstm":
        return X.slstm_apply(p["slstm"], x, cfg.xlstm_cfg())
    raise ValueError(kind)


def _cross_decode(p, x1, cache, acfg: A.AttnConfig):
    B = x1.shape[0]
    hd = acfg.hd
    q = dense(p["wq"], x1).reshape(B, acfg.n_kv_heads, acfg.q_groups, hd)
    qf = q.astype(jnp.float32) / np.sqrt(hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, cache["ck"].astype(jnp.float32))
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", pr, cache["cv"].astype(jnp.float32))
    o = o.reshape(B, 1, acfg.n_heads * hd).astype(x1.dtype)
    return dense(p["wo"], o)


def block_decode(p: Params, x1, cfg: ArchConfig, kind: str, cache: Params,
                 *, layer: int = 0, counters: Optional[List] = None):
    """One-token step.  Returns (x1, new_cache)."""
    if kind == "attn":
        acfg = cfg.attn_cfg()
        sub = {k: v for k, v in cache.items()
               if k in ("k", "v", "len", "k_scale", "v_scale")}
        y, sub = A.decode_step(p["attn"], _norm(cfg, p["attn_norm"], x1),
                               acfg, sub)
        x1 = x1 + y
        new_cache = dict(cache)
        new_cache.update(sub)
        if "cross" in p and "ck" in cache:
            x1 = x1 + _cross_decode(
                p["cross"], _norm(cfg, p["cross_norm"], x1), cache, acfg)
        x1, _ = _apply_ffn_part(p, x1, cfg, layer, counters=counters)
        return x1, new_cache
    if kind == "rec":
        y, st = R.rglru_decode_step(
            p["rec"], _norm(cfg, p["rec_norm"], x1), cfg.rglru_cfg(), cache)
        x1 = x1 + y
        x1, _ = _apply_ffn_part(p, x1, cfg, layer)
        return x1, st
    if kind == "mlstm":
        return X.mlstm_apply(p["mlstm"], x1, cfg.xlstm_cfg(), cache)
    if kind == "slstm":
        return X.slstm_apply(p["slstm"], x1, cfg.xlstm_cfg(), cache)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def _unit_counts(cfg: ArchConfig) -> Tuple[int, int]:
    if cfg.ffn_kinds is not None:
        # per-layer FFN variants have per-layer param SHAPES: nothing to
        # jnp.stack into scan units, so every layer runs on the unscanned
        # "extra" path (ArchConfig validation pins scan_layers=False)
        return 0, cfg.n_layers
    u = len(cfg.pattern)
    return cfg.n_layers // u, cfg.n_layers % u


def _block_kind(cfg: ArchConfig, i: int) -> str:
    """Block kind of absolute layer ``i`` (pattern tiles past one unit)."""
    return cfg.pattern[i % len(cfg.pattern)]


def init_params(key: jax.Array, cfg: ArchConfig) -> Params:
    dtype = cfg.param_dtype
    keys = jax.random.split(key, 8)
    p: Params = {"embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model,
                                     dtype)}
    n_units, rem = _unit_counts(cfg)
    cross = cfg.enc_dec

    def one_unit(k):
        uk = jax.random.split(k, len(cfg.pattern))
        return {f"slot{i}": block_init(uk[i], cfg, kind, cross=cross)
                for i, kind in enumerate(cfg.pattern)}

    unit_keys = jax.random.split(keys[1], max(n_units, 1))
    units = [one_unit(unit_keys[i]) for i in range(n_units)]
    if units:
        p["units"] = jax.tree.map(lambda *xs: jnp.stack(xs), *units)
    off = n_units * len(cfg.pattern)
    rem_keys = jax.random.split(keys[2], max(rem, 1))
    p["extra"] = [block_init(rem_keys[i], cfg, _block_kind(cfg, off + i),
                             cross=cross, layer=off + i)
                  for i in range(rem)]
    p["final_norm"] = _norm_init(cfg, dtype)
    if not cfg.tied_embeddings:
        p["lm_head"] = dense_init(keys[3], cfg.d_model, cfg.vocab_size,
                                  dtype=dtype)
    if cfg.enc_dec:
        ek = jax.random.split(keys[4], cfg.n_enc_layers + 1)
        enc = [block_init(ek[i], cfg, "attn", causal=False)
               for i in range(cfg.n_enc_layers)]
        p["encoder"] = {
            "units": jax.tree.map(lambda *xs: jnp.stack(xs), *enc),
            "final_norm": _norm_init(cfg, dtype),
        }
    if cfg.frontend is not None:
        p["frontend_proj"] = dense_init(keys[5], cfg.d_model, cfg.d_model,
                                        dtype=dtype)
    return p


def param_shapes(cfg: ArchConfig) -> Params:
    """abstract init -- no memory allocated (dry-run path)."""
    return jax.eval_shape(
        functools.partial(init_params, cfg=cfg), jax.random.key(0))


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def encode(params: Params, cfg: ArchConfig, frames: jax.Array) -> jax.Array:
    """Whisper encoder over stub frames (B, T_audio, d)."""
    x = dense(params["frontend_proj"], frames)

    def unit(x, up):
        y, _ = block_apply(up, x, cfg, "attn", causal=False)
        return y, None

    body = jax.checkpoint(unit) if cfg.remat else unit
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["encoder"]["units"])
    else:
        n = jax.tree.leaves(params["encoder"]["units"])[0].shape[0]
        for i in range(n):
            up = jax.tree.map(lambda a: a[i], params["encoder"]["units"])
            x, _ = body(x, up)
    return _norm(cfg, params["encoder"]["final_norm"], x)


def _embed_in(params, cfg: ArchConfig, tokens):
    x = embed(params["embed"], tokens)
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x.astype(cfg.param_dtype)


def forward(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,                       # (B, S) input ids
    *,
    frames: Optional[jax.Array] = None,      # (B, T_audio, d) audio stub
    patches: Optional[jax.Array] = None,     # (B, n_img, d) vision stub
    ffn_taps: Optional[Dict[int, jax.Array]] = None,  # calibration capture
) -> Tuple[jax.Array, jax.Array]:
    """Returns (final hidden (B, S_total, d), aux_loss)."""
    x = _embed_in(params, cfg, tokens)
    prefix_len = None
    if patches is not None:
        img = dense(params["frontend_proj"], patches).astype(x.dtype)
        x = jnp.concatenate([img, x], axis=1)
        if cfg.prefix_lm:
            prefix_len = jnp.asarray(patches.shape[1], jnp.int32)
    memory = encode(params, cfg, frames) if frames is not None else None

    def unit(carry, up):
        x, aux = carry
        # sequence parallelism on the residual stream: the tensor saved per
        # scanned layer (the remat residual) is model-sharded on the token
        # axis, cutting activation memory by the TP degree.  GSPMD re-gathers
        # where a block needs the full sequence.
        x = shard_hint(x, ("pod", "data"), "model", None)
        for i, kind in enumerate(cfg.pattern):
            x, a = block_apply(up[f"slot{i}"], x, cfg, kind,
                               prefix_len=prefix_len, memory=memory)
            aux = aux + a
        return (x, aux), None

    aux = jnp.zeros((), jnp.float32)
    if "units" in params:
        body = jax.checkpoint(unit) if cfg.remat else unit
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(body, (x, aux), params["units"])
        else:
            # unrolled (calibration / small stacks): every layer's ops are
            # visible to cost_analysis, unlike a scanned while body
            n_units = jax.tree.leaves(params["units"])[0].shape[0]
            for i in range(n_units):
                up = jax.tree.map(lambda a: a[i], params["units"])
                (x, aux), _ = body((x, aux), up)
    n_units, _ = _unit_counts(cfg)
    off = n_units * len(cfg.pattern)
    for i, bp in enumerate(params["extra"]):
        x, a = block_apply(bp, x, cfg, _block_kind(cfg, off + i),
                           prefix_len=prefix_len, memory=memory,
                           layer=off + i, taps=ffn_taps)
        aux = aux + a
    return _norm(cfg, params["final_norm"], x), aux


def _logits(params, cfg: ArchConfig, h: jax.Array) -> jax.Array:
    if cfg.tied_embeddings:
        return unembed(params["embed"], h)
    return jnp.dot(h, params["lm_head"]["kernel"],
                   preferred_element_type=jnp.float32)


def lm_loss(
    params: Params,
    cfg: ArchConfig,
    hidden: jax.Array,            # (B, S, d) -- positions aligned w/ inputs
    labels: jax.Array,            # (B, S) next-token targets
    mask: Optional[jax.Array] = None,
    z_loss: float = 1e-4,
) -> jax.Array:
    """Chunk-unrolled stable CE.  Never forms (B, S, V) at once; the python
    loop keeps every chunk's FLOPs visible to cost_analysis."""
    B, S, d = hidden.shape
    mask = jnp.ones((B, S), jnp.float32) if mask is None else mask
    n_chunks = max(1, min(cfg.loss_chunks, S))
    assert S % n_chunks == 0, (S, n_chunks)
    L = S // n_chunks
    total = jnp.zeros((), jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)

    # remat each chunk: backward recomputes its logits instead of keeping
    # n_chunks (B, L, V) residuals alive.
    @jax.checkpoint
    def chunk_ce(h_c, lab_c, m_c):
        lg = _logits(params, cfg, h_c).astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lab_c[..., None], axis=-1)[..., 0]
        out = jnp.sum((lse - gold) * m_c)
        if z_loss:
            out = out + z_loss * jnp.sum(jnp.square(lse) * m_c)
        return out

    for c in range(n_chunks):
        sl = slice(c * L, (c + 1) * L)
        total = total + chunk_ce(hidden[:, sl], labels[:, sl], mask[:, sl])
    return total / denom


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------

def init_caches(cfg: ArchConfig, batch: int, max_len: int) -> Params:
    n_units, rem = _unit_counts(cfg)
    cross_len = cfg.n_frontend_tokens if cfg.enc_dec else 0

    def one_unit():
        return {f"slot{i}": block_init_cache(batch, max_len, cfg, kind,
                                             cross_len=cross_len)
                for i, kind in enumerate(cfg.pattern)}

    caches = {}
    if n_units:
        us = [one_unit() for _ in range(n_units)]
        caches["units"] = jax.tree.map(lambda *xs: jnp.stack(xs), *us)
    off = n_units * len(cfg.pattern)
    caches["extra"] = [
        block_init_cache(batch, max_len, cfg, _block_kind(cfg, off + i),
                         cross_len=cross_len) for i in range(rem)]
    return caches


def prefill(
    params: Params,
    cfg: ArchConfig,
    tokens: jax.Array,
    *,
    frames: Optional[jax.Array] = None,
    patches: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
    counters: Optional[List] = None,
) -> Tuple[jax.Array, Params]:
    """Full forward + cache build.  Returns (last-position logits, caches).
    ``counters`` collects the unscanned layers' MoE counts."""
    B, S = tokens.shape
    x = _embed_in(params, cfg, tokens)
    prefix_len = None
    if patches is not None:
        img = dense(params["frontend_proj"], patches).astype(x.dtype)
        x = jnp.concatenate([img, x], axis=1)
        if cfg.prefix_lm:
            prefix_len = jnp.asarray(patches.shape[1], jnp.int32)
    memory = encode(params, cfg, frames) if frames is not None else None
    total = x.shape[1]
    # the cache must cover the full prefix (incl. modality tokens) + margin
    max_len = max(max_len or 0, total + cfg.decode_margin)

    def unit(x, up):
        # sequence parallelism between blocks (same rationale as training:
        # the residual stream stays model-sharded on tokens; GSPMD gathers
        # only the tiny GQA k/v heads instead of all-reducing activations)
        x = shard_hint(x, ("pod", "data"), "model", None)
        caches = {}
        for i, kind in enumerate(cfg.pattern):
            x, caches[f"slot{i}"] = block_prefill(
                up[f"slot{i}"], x, cfg, kind, max_len,
                prefix_len=prefix_len, memory=memory)
        return x, caches

    caches: Params = {}
    if "units" in params:
        body = jax.checkpoint(unit) if cfg.remat else unit
        if cfg.scan_layers:
            x, caches["units"] = jax.lax.scan(body, x, params["units"])
        else:
            n_units = jax.tree.leaves(params["units"])[0].shape[0]
            per_unit = []
            for i in range(n_units):
                up = jax.tree.map(lambda a: a[i], params["units"])
                x, c = body(x, up)
                per_unit.append(c)
            caches["units"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *per_unit)
    caches["extra"] = []
    n_units, _ = _unit_counts(cfg)
    off = n_units * len(cfg.pattern)
    for i, bp in enumerate(params["extra"]):
        x, c = block_prefill(bp, x, cfg, _block_kind(cfg, off + i), max_len,
                             prefix_len=prefix_len, memory=memory,
                             layer=off + i, counters=counters)
        caches["extra"].append(c)
    h = _norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, h[:, -1:]), caches


def decode_step(
    params: Params,
    cfg: ArchConfig,
    token: jax.Array,                 # (B, 1) last sampled token
    caches: Params,
    counters: Optional[List] = None,
) -> Tuple[jax.Array, Params]:
    """One token for the whole stack.  Returns (logits (B,1,V), caches).
    ``counters`` collects the unscanned layers' MoE counts."""
    x = _embed_in(params, cfg, token)

    def unit(x, scanned):
        up, uc = scanned
        new_c = {}
        for i, kind in enumerate(cfg.pattern):
            x, new_c[f"slot{i}"] = block_decode(
                up[f"slot{i}"], x, cfg, kind, uc[f"slot{i}"])
        return x, new_c

    new_caches: Params = {}
    if "units" in params:
        if cfg.scan_layers:
            x, new_caches["units"] = jax.lax.scan(
                unit, x, (params["units"], caches["units"]))
        else:
            n_units = jax.tree.leaves(params["units"])[0].shape[0]
            per_unit = []
            for i in range(n_units):
                sl = jax.tree.map(lambda a: a[i],
                                  (params["units"], caches["units"]))
                x, c = unit(x, sl)
                per_unit.append(c)
            new_caches["units"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *per_unit)
    new_caches["extra"] = []
    n_units, _ = _unit_counts(cfg)
    off = n_units * len(cfg.pattern)
    for i, bp in enumerate(params["extra"]):
        x, c = block_decode(bp, x, cfg, _block_kind(cfg, off + i),
                            caches["extra"][i], layer=off + i,
                            counters=counters)
        new_caches["extra"].append(c)
    h = _norm(cfg, params["final_norm"], x)
    return _logits(params, cfg, h), new_caches


def greedy_token(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
