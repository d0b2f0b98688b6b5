"""Model backends for the continuous-batching engine (runtime/server.py).

The engine owns slots, the queue and the tick loop; everything model-shaped
lives behind the ``ModelBackend`` protocol:

  * ``init_state``  -- allocate per-slot state (KV-cache lanes, input
                       staging buffers, ...), batch dim = n_slots.
  * ``prefill``     -- stage one admitted request into its slot.
  * ``step``        -- one batched engine iteration over the active slots;
                       appends outputs to the Request objects and marks
                       finished ones ``done``.
  * ``batch_report``-- simulated-hardware accounting for the step that was
                       just executed (VIKIN cycle model), or None when the
                       backend has no hardware model (transformers).

``TransformerBackend`` is the previous Server body (autoregressive decode
over slot KV caches) moved behind the protocol, unchanged.

``MultiWorkloadBackend`` dispatches the same protocol across several named
VIKIN workloads (``--arch a,b,c``): per-workload state lanes, per-request
``workload`` routing, and per-workload ModePlan/cycle accounting, so one
engine process serves a mixed KAN/MLP request population under the
mode-aware batch policies of runtime/scheduler.py.

``VikinBackend`` serves the paper's stacked KAN/MLP feed-forward workloads
(configs/vikin_models.PaperModelConfig): a request is one feature vector,
the batched step pads active slots into a power-of-two shape bucket and runs
the whole stack through the fused v2 KAN / pattern-matmul kernel entry
points in one jitted call, so retrace count is log2(n_slots), not n_slots.
``min_bucket`` defaults to 2 because XLA lowers M=1 contractions through a
different (gemv) path whose accumulation order differs from the gemm tiles;
padding a singleton batch to M=2 keeps batched and one-at-a-time execution
bitwise identical (test-pinned).  The workload's ``ModePlan`` (core/modes)
rides along: every served batch is charged its mode-switch schedule in the
simulated-cycle report.

While a JAX profiler trace runs, ``VikinBackend.step`` and
``TransformerBackend.step`` / ``.prefill`` mark their phases as spans
(runtime/spans.py): ``backend.step`` with its rows active and computed,
split into ``.inputs``, ``.dispatch`` (the jitted call), ``.pick`` (the
transformer's eager greedy pick), ``.readback`` and ``.outputs``;
``backend.prefill`` with its request id and prompt tokens, split into
``.dispatch``, ``.pick``, ``.splice`` (its cache into the slot: one
jitted ``transformer_splice`` call, dispatched before the first token's
``.readback`` so its host time overlaps the prefill on the device).  An
arch with KAN-FFN expert layers also counts, on ``backend.prefill`` and
``backend.step``, the rows its jitted program ``routed`` to the experts
held here and the held experts ``active`` (each summed over the layers),
and the largest group, ``max_group``: read back with the tokens, in the
same ``device_get``.

Implements the backend protocol and cycle-attribution contract of DESIGN.md
Sec. 11; serving calibrated sparse checkpoints (``VikinBackend(masks=...)``,
restored by checkpoint/restore_masks) follows the measurement protocol of
DESIGN.md Sec. 12.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, FrozenSet, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.engine import (
    LayerWork,
    VikinHW,
    run_model,
    serving_report,
)
from repro.core.modes import RECONFIG_CYCLES, ExecMode, LayerKind, ModePlan
from repro.runtime.spans import span
from repro.utils import next_pow2 as _next_pow2


@dataclasses.dataclass
class Request:
    """One serving request.

    ``prompt`` is the request payload: int32 token ids for autoregressive
    backends, a float feature vector for feed-forward (VIKIN) backends.
    Token backends append into ``generated``; one-shot backends set
    ``output``.  ``result()`` returns whichever the backend produced.

    Scheduling fields (runtime/scheduler.py): ``priority`` (higher is more
    urgent; ties broken by arrival), ``deadline_s`` (engine-clock budget
    from submission; the engine counts misses in
    ``stats["deadline_misses"]`` -- at queue-expiry time, not only at
    completion -- and stamps ``met_deadline``), and ``workload`` (which of
    a MultiWorkloadBackend's models serves this request; None for
    single-workload engines).  Overload outcomes (DESIGN.md Sec. 15):
    ``shed`` marks a request evicted by shed admission, ``expired`` one
    dropped past its deadline while queued -- either way it never runs and
    has no result; ``miss_counted`` guards the deadline-miss counter
    against double counting across the queue-expiry scan and the
    completion check.  The ``t_*``/``sim_*`` stamps feed the engine's
    queue-wait / service-latency percentiles in both clocks.
    """

    rid: int
    prompt: np.ndarray
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    output: Optional[np.ndarray] = None
    done: bool = False
    priority: int = 0
    deadline_s: Optional[float] = None
    workload: Optional[str] = None
    met_deadline: Optional[bool] = None
    shed: bool = False
    expired: bool = False
    miss_counted: bool = False
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_done: float = 0.0
    sim_submit: float = 0.0
    sim_admit: float = 0.0
    sim_done: float = 0.0

    def result(self) -> Any:
        return self.generated if self.output is None else self.output


class ModelBackend:
    """Protocol (documented base): the engine calls exactly these four."""

    # Interconnect modes this backend's chips are PINNED to (a frozenset of
    # ExecMode), or None when the hardware reconfigures with the stream.
    # Hetero-plan array backends (runtime/sharded.HeteroVikinBackend) set
    # this; the engine forwards it to the batch policy (SchedContext
    # .pinned_modes) so mode-affinity grouping relaxes for modes that cost
    # nothing to enter (DESIGN.md Sec. 18).
    pinned_modes: Optional[FrozenSet[ExecMode]] = None

    def init_state(self, n_slots: int, max_len: int) -> Any:
        raise NotImplementedError

    def validate(self, req: Request) -> None:
        """Reject malformed payloads at submit time (before the request
        enters the queue), so prefill can never fail mid-run and drop
        already-admitted work."""

    def prefill(self, state: Any, slot: int, req: Request) -> Any:
        """Stage ``req`` into lane ``slot``; returns the new state."""
        raise NotImplementedError

    def step(self, state: Any,
             slot_req: Sequence[Optional[Request]]) -> Any:
        """One batched iteration over active slots; returns the new state.

        Mutates the active Request objects (append outputs, set ``done``).
        """
        raise NotImplementedError

    def batch_report(self, n_active: int,
                     prev_mode: Optional[ExecMode] = None,
                     ) -> Optional[Dict[str, float]]:
        """Simulated-hardware stats for the step just run, or None.

        ``prev_mode`` is the interconnect mode the PREVIOUS served batch
        left the engine in (None = cold start); backends with a cycle model
        charge the carry-over entry flip against it and hand the closing
        mode back under the ``"exit_mode"`` key (an ExecMode the engine
        pops before numeric aggregation) -- the cross-tick mode carry-over
        contract of DESIGN.md Sec. 14.
        """
        return None


# ---------------------------------------------------------------------------
# Transformer (autoregressive) backend -- the original Server body.
# ---------------------------------------------------------------------------


def transformer_layer_works(cfg: Any) -> List[LayerWork]:
    """Per-phase VIKIN LayerWorks for a kan-ffn transformer arch.

    The mode-plan phase mapping of DESIGN.md Sec. 17: every block's
    attention projections are one parallel-mode (MLP) work item, a "kan"
    FFN is a pipeline-mode KAN up-projection (stage-1 basis sparsity)
    followed by a parallel-mode down matmul (stage-2 hidden sparsity), and
    an "mlp" FFN is its two parallel-mode matmuls -- so KAN-FFN phases
    charge pipeline-mode cycles and everything else stays parallel.
    """
    works: List[LayerWork] = []
    hd = cfg.hd
    attn_out = (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + cfg.d_model
    for i in range(cfg.n_layers):
        block = cfg.pattern[i % len(cfg.pattern)]
        if block == "attn":
            works.append(LayerWork(LayerKind.MLP, cfg.d_model, attn_out))
        else:
            # recurrent/xlstm blocks: their gate/proj matmuls are
            # parallel-mode work of roughly d_model x d_model
            works.append(LayerWork(LayerKind.MLP, cfg.d_model, cfg.d_model))
        fk = cfg.layer_ffn_kind(i)
        if fk == "kan":
            fcfg = cfg.ffn_cfg(i)
            up = fcfg.kanffn_up_cfg()
            h = fcfg.kanffn_hidden
            s1 = 1.0 - up.n_bases_kept / up.spec.n_bases
            hm = fcfg.kanffn_hidden_mask()
            s2 = 0.0 if hm is None else float(hm.sparsity)
            works.append(LayerWork(LayerKind.KAN, cfg.d_model, h,
                                   spec=up.spec, pattern_rate=s1))
            works.append(LayerWork(LayerKind.MLP, h, cfg.d_model,
                                   pattern_rate=s2))
        elif fk == "mlp" and cfg.d_ff > 0:
            gated = cfg.ffn_kind in ("swiglu", "geglu")
            up_out = 2 * cfg.d_ff if gated else cfg.d_ff
            works.append(LayerWork(LayerKind.MLP, cfg.d_model, up_out))
            works.append(LayerWork(LayerKind.MLP, cfg.d_ff, cfg.d_model))
        elif fk == "moe":
            # top_k expert FFNs' worth of parallel-mode work per token
            k = max(cfg.top_k, 1)
            works.append(LayerWork(LayerKind.MLP, cfg.d_model,
                                   2 * k * cfg.d_ff))
            works.append(LayerWork(LayerKind.MLP, k * cfg.d_ff, cfg.d_model))
    return works


class TransformerBackend(ModelBackend):
    """Slot KV-cache decode for ArchConfig transformer stacks.

    ``impl`` / ``masks`` / ``precision`` mirror VikinBackend's plumbing for
    kan-ffn archs (cfg.ffn_kinds set): ``impl`` selects the kernel dispatch
    of every kan-ffn layer, ``masks`` installs calibrated per-layer
    (basis_keep, hidden_keep) pairs (core/calibrate.calibrate_kanffn_masks),
    and ``precision`` picks f32 or bf16 serving (params cast once here).
    Such archs also gain the VIKIN cycle model: a per-layer ModePlan
    (attention/down phases parallel, KAN up-projections pipeline) charged
    through ``batch_report`` with the cross-tick mode carry-over contract,
    counting one model instance per decoded token plus one per prefilled
    prompt token.  Plain archs keep batch_report() -> None.
    """

    def __init__(self, cfg: Any, params: Any, *,
                 impl: Optional[str] = None, masks: Any = None,
                 precision: str = "f32",
                 hw: Optional[VikinHW] = None) -> None:
        import jax

        from repro.models import transformer as T

        if precision not in ("f32", "bf16"):
            raise ValueError(
                f"TransformerBackend serves f32|bf16, got {precision!r} "
                "(int8 transformer serving is not supported; the vikin "
                "backends own the quantized path)")
        if masks is not None:
            cfg = dataclasses.replace(cfg, ffn_masks=tuple(masks))
        if impl is not None and cfg.ffn_kinds is not None:
            cfg = dataclasses.replace(cfg, ffn_impl=impl)
        # bf16 casts any arch; f32 casts only kan-ffn archs (a bf16-configured
        # kan-ffn arch served at f32 runs f32), while plain archs served at
        # the default keep their configured dtype
        kanffn = cfg.ffn_kinds is not None
        dtype = ("bfloat16" if precision == "bf16"
                 else "float32" if kanffn else cfg.dtype)
        if cfg.dtype != dtype:
            import jax.numpy as jnp

            cfg = dataclasses.replace(cfg, dtype=dtype)
            params = jax.tree.map(
                lambda a: (a.astype(cfg.param_dtype)
                           if jnp.issubdtype(a.dtype, jnp.floating) else a),
                params)
        self.cfg, self.params = cfg, params
        self.precision = precision
        from repro.models.moe import total_counts

        self._T, self._jax, self._total_counts = T, jax, total_counts
        # the jitted programs return the MoE counts beside the logits
        # (models/moe.total_counts: empty for an arch without MoE layers)
        def transformer_decode(p: Any, tok: Any, c: Any) -> Any:
            counters: List[Any] = []
            logits, c = T.decode_step(p, cfg, tok, c, counters=counters)
            return logits, c, total_counts(counters)

        self._decode = jax.jit(transformer_decode)

        def transformer_splice(caches: Any, cache: Any, slot: Any) -> Any:
            # write the fresh batch-1 cache into lane ``slot`` of every
            # leaf: the batch dim is the first of dims 0, 1 where the slot
            # caches are n_slots wide and the fresh one 1 wide (dim 0 for
            # the extra list, dim 1 under the unit stack).  Decided from
            # the shapes at trace time; ``slot`` is traced, so one
            # executable serves every slot.
            def put(full: Any, new: Any) -> Any:
                for d in range(min(2, full.ndim, new.ndim)):
                    if full.shape[d] == self.n_slots and new.shape[d] == 1:
                        return jax.lax.dynamic_update_slice_in_dim(
                            full, new.astype(full.dtype), slot, axis=d)
                return full

            return jax.tree.map(put, caches, cache)

        self._splice = jax.jit(transformer_splice)
        # prefill is jitted per exact prompt length: no padding, so slot
        # caches carry the true per-request position (the per-row 'len').
        self._prefill_cache: Dict[int, Callable[..., Any]] = {}
        self.n_slots: Optional[int] = None
        self.max_len: Optional[int] = None
        self.hw = hw or VikinHW()
        self.plan: Optional[ModePlan] = None
        self.layers: Optional[List[LayerWork]] = None
        if cfg.ffn_kinds is not None:
            self.layers = transformer_layer_works(cfg)
            self.plan = ModePlan.for_layers([w.kind for w in self.layers])
        self._pending_prefill = 0
        self._report_cache: Dict[Tuple[int, int, Optional[ExecMode]],
                                 Dict[str, float]] = {}

    def init_state(self, n_slots: int, max_len: int) -> Any:
        self.n_slots, self.max_len = n_slots, max_len
        return self._T.init_caches(self.cfg, n_slots, max_len)

    def _prefill_fn(self, length: int) -> Callable[..., Any]:
        if length not in self._prefill_cache:
            cfg, T = self.cfg, self._T

            def transformer_prefill(params: Any, tokens: Any) -> Any:
                counters: List[Any] = []
                logits, cache = T.prefill(params, cfg, tokens,
                                          max_len=self.max_len,
                                          counters=counters)
                return logits, cache, self._total_counts(counters)

            self._prefill_cache[length] = self._jax.jit(transformer_prefill)
        return self._prefill_cache[length]

    def prefill_logits(self, tokens: np.ndarray) -> Tuple[Any, Any]:
        """(last-position logits, batch caches) of the served prefill for
        a (batch, length) int32 token array."""
        import jax.numpy as jnp

        return self._prefill_fn(tokens.shape[1])(
            self.params, jnp.asarray(tokens, jnp.int32))[:2]

    def prefill(self, caches: Any, slot: int, req: Request) -> Any:
        """Prefill one request and splice its (batch=1) cache into lane
        ``slot`` of the server's (batch=n_slots) caches."""
        import jax.numpy as jnp

        jax, T = self._jax, self._T
        tokens = np.asarray(req.prompt, np.int32)[None, :]
        if self.layers is not None:
            # each prefilled prompt token is one model instance the cycle
            # model must charge on the NEXT tick's report
            self._pending_prefill += tokens.shape[1]
        with span("backend.prefill", rid=req.rid,
                  tokens=tokens.shape[1]) as sp:
            with span("backend.prefill.dispatch"):
                logits, cache, counts = self._prefill_fn(tokens.shape[1])(
                    self.params, jnp.asarray(tokens, jnp.int32))
            with span("backend.prefill.pick"):
                first = T.greedy_token(logits)
            with span("backend.prefill.splice"):
                caches = self._splice(caches, cache, np.int32(slot))
            with span("backend.prefill.readback"):
                first, counts = jax.device_get((first, counts))
            sp.count(**counts)
            req.generated.append(int(first[0, 0]))
        return caches

    def step(self, caches: Any,
             slot_req: Sequence[Optional[Request]]) -> Any:
        import jax.numpy as jnp

        jax, T = self._jax, self._T
        active = [s for s, r in enumerate(slot_req) if r is not None]
        with span("backend.step", active=len(active),
                  computed=self.n_slots) as sp:
            with span("backend.step.inputs"):
                toks = np.zeros((self.n_slots, 1), np.int32)
                for s in active:
                    toks[s, 0] = slot_req[s].generated[-1]
                toks = jnp.asarray(toks)
            with span("backend.step.dispatch"):
                logits, caches, counts = self._decode(self.params, toks,
                                                      caches)
            with span("backend.step.pick"):
                picked = T.greedy_token(logits)
            with span("backend.step.readback"):
                nxt, counts = jax.device_get((picked, counts))
            sp.count(**counts)
            with span("backend.step.outputs"):
                for s in active:
                    req = slot_req[s]
                    tok = int(nxt[s, 0])
                    req.generated.append(tok)
                    if (len(req.generated) >= req.max_new_tokens
                            or (req.eos_id is not None
                                and tok == req.eos_id)):
                        req.done = True
        return caches

    def batch_report(self, n_active: int,
                     prev_mode: Optional[ExecMode] = None,
                     ) -> Optional[Dict[str, float]]:
        """VIKIN cycle model for the tick just run (kan-ffn archs only).

        ``batch`` = one model instance per active decode slot plus one per
        prompt token prefilled since the last report; ``prev_mode`` is the
        carried interconnect state (DESIGN.md Sec. 14) and ``exit_mode``
        hands the closing state back to the engine.  Plain archs (no
        ffn_kinds) return None -- no hardware model.
        """
        if self.layers is None:
            return None
        pending, self._pending_prefill = self._pending_prefill, 0
        batch = n_active + pending
        if batch <= 0:
            return None
        key = (n_active, pending, prev_mode)
        if key not in self._report_cache:
            self._report_cache[key] = serving_report(
                self.layers, self.hw, batch=batch,
                prev_mode=prev_mode, precision=self.precision)
        return dict(self._report_cache[key])

    def cycle_attribution(self, batch: int,
                          prev_mode: Optional[ExecMode] = None,
                          ) -> Dict[str, object]:
        """Per-layer-phase cycle split whose parts sum EXACTLY to the
        serving report's sim_cycles at the same (batch, prev_mode):
        sum(per_layer_cycles) + reconfig_cycles == sim_cycles
        (test-pinned: tests/test_kanffn_serving.py)."""
        if self.layers is None:
            raise ValueError("cycle_attribution needs a kan-ffn arch "
                             "(cfg.ffn_kinds set)")
        rep = run_model(self.layers, self.hw, batch=batch)
        switches, _ = self.plan.stream_switches(batch, prev_mode)
        return {
            "per_layer_cycles": [float(lc.total * batch)
                                 for lc in rep.per_layer],
            "reconfig_cycles": float(switches * RECONFIG_CYCLES),
        }


# ---------------------------------------------------------------------------
# VIKIN backend -- stacked KAN/MLP feed-forward serving.
# ---------------------------------------------------------------------------


class VikinBackend(ModelBackend):
    """Serve a PaperModelConfig KAN/MLP stack through the fused kernels.

    Each request carries one ``(n_in,)`` float32 feature vector and finishes
    in a single engine tick.  Active slots are gathered into a zero-padded
    power-of-two batch bucket (>= ``min_bucket``) and run through one jitted
    forward, so the jit cache holds one entry per bucket, not per batch
    size.  ``plan`` is the workload's host-issued mode-switch schedule; the
    per-batch simulated cycles (batch_report) include its reconfiguration
    charge via core/engine.run_model.

    ``precision`` selects the served numerics: "f32" (default), "bf16"
    (params + activations cast, f32 out), or "int8" (post-training
    quantized path, core/quant) -- int8 requires the calibrated
    ``scales`` (core/calibrate.calibrate_scales or a checkpoint's
    restore_scales); params are quantized ONCE here and the quantized
    forward runs per step.  Requests still submit f32 payloads at every
    precision; the cycle model charges precision-dependent DMA bytes.
    """

    def __init__(self, model: Any, params: Any, *, impl: str = "auto",
                 hw: Optional[VikinHW] = None, min_bucket: int = 2,
                 nnz_rates: Optional[Sequence[float]] = None,
                 masks: Any = None, precision: str = "f32",
                 scales: Any = None) -> None:
        import jax

        if precision not in ("f32", "bf16", "int8"):
            raise ValueError(
                f"unknown precision {precision!r}; expected f32|bf16|int8")
        if precision == "int8":
            if scales is None:
                raise ValueError(
                    "precision='int8' requires calibrated scales "
                    "(core/calibrate.calibrate_scales or "
                    "checkpoint.restore_scales)")
            from repro.core.quant import quantize_stack_params
            params = quantize_stack_params(params, model, scales)
        elif precision == "bf16":
            import jax.numpy as jnp
            params = jax.tree.map(
                lambda a: jnp.asarray(a, jnp.bfloat16), params)
        self.model, self.params = model, params
        self.impl, self.hw = impl, hw or VikinHW()
        self.precision, self.scales = precision, scales
        self.array = None          # multi-chip model (runtime/sharded.py)
        self.min_bucket = min_bucket
        self.masks = list(masks) if masks is not None else None
        self.plan = ModePlan.for_layers(model.layer_kind_enums())
        if self.masks is not None:
            # calibrated model: charge the cycle model the MEASURED
            # per-layer mask sparsity, not the config-level rate
            from repro.core.calibrate import masked_pattern_rates
            self.layers = model.layer_works(
                nnz_rates, pattern_rates=masked_pattern_rates(self.masks))
        else:
            self.layers = model.layer_works(nnz_rates)
        self.n_in = int(model.sizes[0])
        self._fwd = jax.jit(self.forward_fn())
        self._report_cache: Dict[Tuple[int, Optional[ExecMode]],
                                 Dict[str, float]] = {}
        self.n_slots: Optional[int] = None

    def forward_fn(self) -> Callable[[Any, Any], Any]:
        """The raw batched forward ``(params, x) -> y`` this backend jits;
        the ONE definition of what a VIKIN forward is.  ShardedVikinBackend
        wraps exactly this in shard_map, so the two backends cannot
        drift."""
        from repro.models.ffn import vikin_stack_apply

        model, impl, masks = self.model, self.impl, self.masks
        precision, scales = self.precision, self.scales

        def vikin_forward(p: Any, x: Any) -> Any:
            if precision == "int8":
                from repro.core.quant import quant_stack_apply

                return quant_stack_apply(p, x, model, scales, impl=impl,
                                         masks=masks)
            if precision == "bf16":
                import jax.numpy as jnp

                return vikin_stack_apply(
                    p, x.astype(jnp.bfloat16), model, impl=impl,
                    masks=masks).astype(jnp.float32)
            return vikin_stack_apply(p, x, model, impl=impl, masks=masks)

        return vikin_forward

    def init_state(self, n_slots: int, max_len: int) -> np.ndarray:
        self.n_slots = n_slots
        # staging buffer of request inputs, one lane per slot
        return np.zeros((n_slots, self.n_in), np.float32)

    def input_dim(self, workload: Optional[str] = None) -> int:
        """Feature width a request payload must have (trace replay uses
        this to synthesize payloads from per-event seeds)."""
        return self.n_in

    def validate(self, req: Request) -> None:
        vec = np.asarray(req.prompt, np.float32).reshape(-1)
        if vec.shape[0] != self.n_in:
            raise ValueError(
                f"request {req.rid}: payload has {vec.shape[0]} features, "
                f"model {self.model.name!r} expects {self.n_in}")

    def prefill(self, inputs: np.ndarray, slot: int,
                req: Request) -> np.ndarray:
        inputs = inputs.copy()
        inputs[slot] = np.asarray(req.prompt, np.float32).reshape(-1)
        return inputs

    def bucket(self, n_active: int) -> int:
        """Always a power of two (>= min_bucket), even for non-pow2 slot
        counts: padding a few extra rows is cheaper than running a batch
        shape outside the pinned bitwise-determinism regime."""
        return _next_pow2(max(n_active, self.min_bucket))

    def warmup(self, n_active: int) -> None:
        """Pre-trace the bucket that ``n_active`` requests would use, so
        benchmarks can keep compilation out of their timed region."""
        self._fwd(self.params,
                  np.zeros((self.bucket(n_active), self.n_in), np.float32))

    def step(self, inputs: np.ndarray,
             slot_req: Sequence[Optional[Request]]) -> np.ndarray:
        active = [s for s, r in enumerate(slot_req) if r is not None]
        bucket = self.bucket(len(active))
        with span("backend.step", active=len(active), computed=bucket):
            with span("backend.step.inputs"):
                xb = np.zeros((bucket, self.n_in), np.float32)
                for j, s in enumerate(active):
                    xb[j] = inputs[s]
            with span("backend.step.dispatch"):
                y = self._fwd(self.params, xb)
            with span("backend.step.readback"):
                y = np.asarray(y)
            with span("backend.step.outputs"):
                for j, s in enumerate(active):
                    slot_req[s].output = y[j].copy()
                    slot_req[s].done = True
        return inputs

    def batch_report(self, n_active: int,
                     prev_mode: Optional[ExecMode] = None,
                     ) -> Dict[str, float]:
        """VIKIN cycle model for one served batch (batches stream
        sequentially through the single engine instance, so compute cycles
        scale linearly in n_active and every instance pays its mode plan).
        ``prev_mode`` is the carried interconnect state from the previous
        batch (DESIGN.md Sec. 14): entering from a disagreeing mode costs
        one extra RECONFIG_CYCLES flip, and the report's ``exit_mode``
        hands the closing state back to the engine.  ``self.array`` (set
        by ShardedVikinBackend) swaps in the multi-chip report."""
        key = (n_active, prev_mode)
        if key not in self._report_cache:
            self._report_cache[key] = serving_report(
                self.layers, self.hw, batch=n_active, array=self.array,
                prev_mode=prev_mode, precision=self.precision)
        return dict(self._report_cache[key])


# ---------------------------------------------------------------------------
# Multi-workload dispatch -- several VIKIN models behind one engine.
# ---------------------------------------------------------------------------


class MultiWorkloadBackend(ModelBackend):
    """Serve several named workloads (``--arch a,b,c``) from one engine.

    Wraps a dict of per-workload backends behind the single ModelBackend
    protocol: every request carries a ``workload`` name, per-workload state
    lanes are kept side by side (input widths differ across models), and
    ``step`` runs one batched forward per workload present among the active
    slots.  The batch policy (runtime/scheduler.py) keeps each tick's
    admitted set single-workload, so in steady state a tick is exactly one
    sub-backend forward -- the grouping that lets the mode carry-over
    contract amortize ``RECONFIG_CYCLES`` across requests.

    ``batch_report`` threads the carried interconnect mode through the
    sub-backends in the order they executed and accumulates a per-workload
    view (``workload_stats``: served / batches / sim cycles / mode flips
    per workload) next to the engine's global stats.
    """

    def __init__(self, backends: Dict[str, ModelBackend]) -> None:
        if not backends:
            raise ValueError("MultiWorkloadBackend needs >= 1 workload")
        self.backends = dict(backends)
        self.plans: Dict[str, ModePlan] = {
            n: b.plan for n, b in self.backends.items()
            if hasattr(b, "plan")}
        self.workload_stats: Dict[str, Dict[str, float]] = {
            n: {} for n in self.backends}
        # (workload, n_active, n_done) per sub-backend stepped this tick
        self._last_served: List[Tuple[str, int, int]] = []

    def bucket_for(self, workload: str, n_active: int) -> int:
        """Padding bucket the named workload would run ``n_active``
        requests in (scheduler's zero-padding-waste signal)."""
        b = self.backends[workload]
        return b.bucket(n_active) if hasattr(b, "bucket") else n_active

    @property
    def pinned_modes(self) -> Optional[FrozenSet[ExecMode]]:
        """Union of the sub-backends' chip pins, but only when EVERY
        mode-planned sub-backend is pinned (hetero array plan) -- a single
        reconfiguring sub-backend means flips still cost somewhere, so the
        scheduler must keep grouping (None)."""
        pins = set()
        for name, b in self.backends.items():
            p = getattr(b, "pinned_modes", None)
            if p is None:
                if name in self.plans:
                    return None
                continue
            pins |= set(p)
        return frozenset(pins) if pins else None

    def input_dim(self, workload: Optional[str] = None) -> int:
        """Feature width of the named workload's payloads (trace replay)."""
        if workload not in self.backends:
            raise ValueError(
                f"input_dim: unknown workload {workload!r}; this engine "
                f"serves {sorted(self.backends)}")
        return self.backends[workload].input_dim()

    def init_state(self, n_slots: int, max_len: int) -> Dict[str, Any]:
        return {n: b.init_state(n_slots, max_len)
                for n, b in self.backends.items()}

    def validate(self, req: Request) -> None:
        if req.workload not in self.backends:
            raise ValueError(
                f"request {req.rid}: unknown workload {req.workload!r}; "
                f"this engine serves {sorted(self.backends)}")
        self.backends[req.workload].validate(req)

    def prefill(self, state: Dict[str, Any], slot: int,
                req: Request) -> Dict[str, Any]:
        state = dict(state)
        state[req.workload] = self.backends[req.workload].prefill(
            state[req.workload], slot, req)
        return state

    def step(self, state: Dict[str, Any],
             slot_req: Sequence[Optional[Request]]) -> Dict[str, Any]:
        state = dict(state)
        order: List[str] = []
        for r in slot_req:
            if r is not None and r.workload not in order:
                order.append(r.workload)
        self._last_served = []
        for name in order:
            view = [r if (r is not None and r.workload == name) else None
                    for r in slot_req]
            state[name] = self.backends[name].step(state[name], view)
            active = [r for r in view if r is not None]
            # completions counted off req.done, not slot-steps, so the
            # per-workload served totals stay correct for multi-tick
            # (token) sub-backends too
            self._last_served.append(
                (name, len(active), sum(1 for r in active if r.done)))
        return state

    def batch_report(self, n_active: int,
                     prev_mode: Optional[ExecMode] = None,
                     ) -> Optional[Dict[str, float]]:
        total: Dict[str, float] = {}
        mode = prev_mode
        for name, k, n_done in self._last_served:
            rep = self.backends[name].batch_report(k, prev_mode=mode)
            ws = self.workload_stats[name]
            ws["served"] = ws.get("served", 0.0) + n_done
            ws["batches"] = ws.get("batches", 0.0) + 1
            if rep is None:
                continue
            rep = dict(rep)
            mode = rep.pop("exit_mode", mode)
            for key, v in rep.items():
                total[key] = total.get(key, 0.0) + v
                ws[key] = ws.get(key, 0.0) + v
        if mode is not None:
            total["exit_mode"] = mode
        return total if total else None
