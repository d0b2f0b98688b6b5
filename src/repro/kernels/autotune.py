"""Block-size autotuning for the Pallas kernels (kan_fused / pattern_matmul /
spline_basis).

The three kernels ship sensible MXU-aligned default tiles, but the best
(bm, bi/bk, bn) depends on the layer shape, dtype and generation of the part:
a KAN-FFN up-projection (B*T x d_model -> h) and the down-projection
(B*T x h -> d_model) want different tiles, and bf16 halves the VMEM cost of
every block.  This module provides

  * a *persistent* JSON cache keyed by (kernel, shape bucket, dtype, backend),
  * a measured search over a pruned candidate grid (``tune_*`` entry points),
  * a lookup used by every kernel's ``impl="auto"`` dispatch, so a shape tuned
    once is served tuned tiles forever after (including across processes).

Shapes are bucketed to the next power of two per dimension so one search
covers the whole jit-retrace neighbourhood; the backend is part of the key so
CPU/interpret timings never masquerade as TPU tunings.

Cache file: ``$REPRO_AUTOTUNE_CACHE`` if set, else ``.autotune/autotune.json``
at the root of the checkout (gitignored, so a fresh checkout serves the
kernel defaults).  Format documented in DESIGN.md Sec. 9.

Search-on-miss is opt-in (``REPRO_AUTOTUNE=1`` or ``autotune=True`` on the
``tune_*`` wrappers): a silent multi-second search in the middle of a serving
step is worse than a default tile.

Implements DESIGN.md Sec. 9 (cache key/format, candidate pruning, the
bucketing rationale); the per-kernel block knobs it feeds are defined there
too.  Tuned tiles reach the serving stack through ``KANConfig.blocks`` /
``FFNConfig.kan_blocks`` and each kernel's ``impl="auto"`` dispatch.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.utils import REPO_ROOT
from repro.utils import next_pow2 as _next_pow2

CACHE_SCHEMA_VERSION = 1

# VMEM budget used to prune candidate tiles (bytes, conservative half of the
# ~16 MiB/core so double-buffered pipelines still fit).
VMEM_BUDGET = 8 * 1024 * 1024

# Ring buffer of (kernel, key, blocks, source) records appended by the
# impl="auto" dispatchers -- lets tests (and humans) confirm that a tuned
# shape is actually served its cached tiles.
DISPATCH_LOG: List[Tuple[str, str, Dict[str, int], str]] = []
_DISPATCH_LOG_MAX = 256


def note_dispatch(kernel: str, key: str, blocks: Dict[str, int],
                  source: str) -> None:
    DISPATCH_LOG.append((kernel, key, dict(blocks), source))
    if len(DISPATCH_LOG) > _DISPATCH_LOG_MAX:
        del DISPATCH_LOG[: len(DISPATCH_LOG) - _DISPATCH_LOG_MAX]


def default_cache_path() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE", "")
    if env:
        return env
    return str(REPO_ROOT / ".autotune" / "autotune.json")


def shape_bucket(dims: Sequence[int]) -> Tuple[int, ...]:
    """Round every dim up to the next power of two (>= 1)."""
    return tuple(_next_pow2(max(1, int(d))) for d in dims)


def cache_key(kernel: str, dims: Sequence[int], dtype,
              backend: Optional[str] = None) -> str:
    backend = backend or jax.default_backend()
    bucket = "x".join(str(d) for d in shape_bucket(dims))
    return f"{kernel}|{bucket}|{jnp.dtype(dtype).name}|{backend}"


class AutotuneCache:
    """Persistent {cache_key: {"blocks": {...}, "us": float}} JSON store."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        self._data: Optional[Dict[str, Dict]] = None
        self._discard_disk = False      # set by clear(): next save resets

    # -- persistence -------------------------------------------------------
    def _read_disk(self) -> Dict[str, Dict]:
        try:
            with open(self.path) as f:
                raw = json.load(f)
            if raw.get("schema") == CACHE_SCHEMA_VERSION:
                return dict(raw.get("entries", {}))
        except (OSError, ValueError):
            pass
        return {}

    def _load(self) -> Dict[str, Dict]:
        if self._data is None:
            self._data = self._read_disk()
        return self._data

    def save(self) -> None:
        data = self._load()
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        # Re-read and merge the on-disk entries before writing: this
        # process's in-memory view may predate entries another process
        # (concurrent CI job, sharded run) persisted since our first load,
        # and rewriting only our view would silently drop theirs.  Our own
        # entries win on key conflicts (they carry this process's fresher
        # timing).  The tmp+rename below keeps every write atomic; the
        # read->rename window is not locked, so two processes racing on the
        # SAME key still last-write-wins -- but disjoint keys (the CI case)
        # are never lost.
        disk = {} if self._discard_disk else self._read_disk()
        merged = {**disk, **data}
        self._data, self._discard_disk = merged, False
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            # allow_nan=False keeps the file strict RFC-8259 JSON (readable
            # by jq / JS / strict parsers), not just Python-round-trippable.
            json.dump({"schema": CACHE_SCHEMA_VERSION, "entries": merged},
                      f, indent=1, sort_keys=True, allow_nan=False)
        os.replace(tmp, self.path)

    # -- access ------------------------------------------------------------
    def lookup(self, key: str) -> Optional[Dict[str, int]]:
        ent = self._load().get(key)
        if ent is None:
            return None
        return {k: int(v) for k, v in ent["blocks"].items()}

    def store(self, key: str, blocks: Dict[str, int],
              us: Optional[float] = None, persist: bool = True) -> None:
        self._load()[key] = {"blocks": {k: int(v) for k, v in blocks.items()},
                             "us": None if us is None else float(us)}
        if persist:
            self.save()

    def clear(self) -> None:
        """Reset to empty: the next save() overwrites rather than merges
        (an explicit reset is the one case where dropping the on-disk
        entries is the point)."""
        self._data = {}
        self._discard_disk = True


_GLOBAL_CACHE: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    # Trace-time global by design: block lookups are static compile-time
    # config (the same cache state always resolves the same blocks for a
    # shape), so memoizing the cache object across traces is deliberate.
    global _GLOBAL_CACHE  # vikinlint: disable=VL003
    if _GLOBAL_CACHE is None or _GLOBAL_CACHE.path != default_cache_path():
        _GLOBAL_CACHE = AutotuneCache()
    return _GLOBAL_CACHE


def lookup_blocks(kernel: str, dims: Sequence[int], dtype,
                  cache: Optional[AutotuneCache] = None,
                  backend: Optional[str] = None,
                  ) -> Optional[Dict[str, int]]:
    """Cached blocks for a shape, or None.  Logs the hit for observability.

    ``backend`` namespaces the key exactly like ``search``/``tune_*`` do
    when storing (interpret-mode tuning stores under "cpu"); None means
    the current jax backend, so lookups match what was tuned HERE.
    """
    cache = cache or get_cache()
    key = cache_key(kernel, dims, dtype, backend)
    blocks = cache.lookup(key)
    if blocks is not None:
        note_dispatch(kernel, key, blocks, "cache")
    return blocks


def autotune_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "0") not in ("", "0", "false")


# ---------------------------------------------------------------------------
# Generic measured search.
# ---------------------------------------------------------------------------


def _time_once(fn: Callable[[], jax.Array], reps: int) -> float:
    jax.block_until_ready(fn())          # compile outside the timed region
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps * 1e6


def search(
    kernel: str,
    dims: Sequence[int],
    dtype,
    run_fn: Callable[..., jax.Array],
    candidates: Iterable[Dict[str, int]],
    *,
    reps: int = 3,
    cache: Optional[AutotuneCache] = None,
    persist: bool = True,
    backend: Optional[str] = None,
) -> Dict[str, int]:
    """Time ``run_fn(**cand)`` per candidate, cache and return the winner.

    Candidates that fail to compile/run (e.g. a tile shape Mosaic rejects on
    this part) are skipped rather than fatal.  ``backend`` overrides the
    cache-key backend: interpret-mode searches pass "cpu" (interpret runs on
    the host) so their timings are never served to a real accelerator
    dispatch.
    """
    cache = cache or get_cache()
    key = cache_key(kernel, dims, dtype, backend)
    best: Optional[Tuple[float, Dict[str, int]]] = None
    for cand in candidates:
        try:
            us = _time_once(lambda: run_fn(**cand), reps)
        except Exception:
            continue
        if best is None or us < best[0]:
            best = (us, dict(cand))
    if best is None:
        raise RuntimeError(f"autotune: no candidate ran for {key}")
    cache.store(key, best[1], us=best[0], persist=persist)
    note_dispatch(kernel, key, best[1], "search")
    return best[1]


# ---------------------------------------------------------------------------
# Per-kernel candidate grids (pruned by a conservative VMEM estimate).
# ---------------------------------------------------------------------------


def _dtype_bytes(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def candidates_kan_fused(B: int, n_in: int, n_out: int, nbk: int,
                         dtype, order: int = 3) -> List[Dict[str, int]]:
    """(bm, bi, bn) grid for the fused v2 KAN kernel: only tiles the kernel
    runs as given -- fitted to the shape (``fit_blocks``' lane/sublane
    rules) and within its scoped-VMEM limit under the lane-padded
    ``vmem_bytes`` model."""
    from repro.kernels.kan_fused.kan_fused import VMEM_LIMIT, vmem_bytes
    from repro.kernels.tiling import LANES, fit_block, fit_rows

    out: List[Dict[str, int]] = []
    for bm in sorted({fit_rows(b, B, dtype) for b in (8, 32, 128, 512)}):
        for bi in sorted({fit_block(b, n_in, LANES) for b in (128, 256)}):
            for bn in sorted({fit_block(b, n_out, LANES)
                              for b in (128, 256, 512)}):
                if vmem_bytes(bm, bi, bn, nbk, dtype, order) <= VMEM_LIMIT:
                    out.append({"bm": bm, "bi": bi, "bn": bn})
    return out


def candidates_pattern_matmul(M: int, K: int, N: int,
                              dtype) -> List[Dict[str, int]]:
    """(bm, bk, bn) grid for the compact matmul, fitted to the shape by the
    same lane/sublane rules the kernel applies, within ``VMEM_BUDGET``
    counting double-buffered lane-padded blocks and the f32 accumulator."""
    from repro.kernels.tiling import LANES, fit_block, fit_rows, padded_bytes

    out: List[Dict[str, int]] = []
    for bm in sorted({fit_rows(b, M, dtype) for b in (8, 32, 128, 512)}):
        for bk in sorted({fit_block(b, K, LANES) for b in (128, 512, 1024)}):
            for bn in sorted({fit_block(b, N, LANES)
                              for b in (128, 256, 512)}):
                vmem = (2 * (padded_bytes(bm, bk, dtype)
                             + padded_bytes(bk, bn, dtype)
                             + padded_bytes(bm, bn, jnp.float32))
                        + padded_bytes(bm, bn, jnp.float32))
                if vmem <= VMEM_BUDGET:
                    out.append({"bm": bm, "bk": bk, "bn": bn})
    return out


def candidates_spline_basis(n: int, n_bases: int, dtype) -> List[Dict[str, int]]:
    eb = _dtype_bytes(dtype)
    out = []
    for block_n in (256, 512, 1024, 2048, 4096):
        if block_n > _next_pow2(max(256, n)):
            continue
        if block_n * (1 + n_bases) * eb <= VMEM_BUDGET:
            out.append({"block_n": block_n})
    return out or [{"block_n": 256}]


# ---------------------------------------------------------------------------
# Concrete tuners (imported lazily to avoid import cycles with the kernels).
# ---------------------------------------------------------------------------


def tune_kan_fused(x, w_b, t_flat, spec, kb=None, *, version: int = 2,
                   interpret: bool = False, reps: int = 3,
                   cache: Optional[AutotuneCache] = None) -> Dict[str, int]:
    from repro.kernels.kan_fused.kan_fused import (
        kan_fused_pallas, kan_fused_pallas_v2)
    from repro.kernels.kan_fused.ops import fuse_wt

    B, n_in = x.shape
    n_out = w_b.shape[1]
    kb = tuple(range(spec.n_bases)) if kb is None else tuple(kb)
    nbk = len(kb)
    cands = candidates_kan_fused(B, n_in, n_out, nbk, x.dtype, spec.order)
    if version == 2:
        wt = fuse_wt(w_b, t_flat, nbk)
        run = lambda bm, bi, bn: kan_fused_pallas_v2(
            x, wt, spec, kb, bm=bm, bi=bi, bn=bn, interpret=interpret)
    else:
        run = lambda bm, bi, bn: kan_fused_pallas(
            x, w_b, t_flat, spec, kb, bm=bm, bi=bi, bn=bn,
            interpret=interpret)
    name = f"kan_fused_v{version}"
    return search(name, (B, n_in, n_out, nbk), x.dtype, run, cands,
                  reps=reps, cache=cache,
                  backend="cpu" if interpret else None)


def tune_pattern_matmul(x_c, w_c, bias=None, *, act=None,
                        interpret: bool = False, reps: int = 3,
                        cache: Optional[AutotuneCache] = None
                        ) -> Dict[str, int]:
    from repro.kernels.pattern_matmul.pattern_matmul import (
        matmul_compact_pallas)

    M, K = x_c.shape
    N = w_c.shape[1]
    cands = candidates_pattern_matmul(M, K, N, x_c.dtype)
    run = lambda bm, bk, bn: matmul_compact_pallas(
        x_c, w_c, bias, act=act, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return search("pattern_matmul", (M, K, N), x_c.dtype, run, cands,
                  reps=reps, cache=cache,
                  backend="cpu" if interpret else None)


def tune_spline_basis(x, spec, *, interpret: bool = False, reps: int = 3,
                      cache: Optional[AutotuneCache] = None
                      ) -> Dict[str, int]:
    from repro.kernels.spline_basis.spline_basis import spline_basis_pallas

    (n,) = x.shape
    cands = candidates_spline_basis(n, spec.n_bases, x.dtype)
    run = lambda block_n: spline_basis_pallas(
        x, spec, block_n=block_n, interpret=interpret)
    return search("spline_basis", (n, spec.n_bases), x.dtype, run, cands,
                  reps=reps, cache=cache,
                  backend="cpu" if interpret else None)
