"""Data-parallel sharded VIKIN serving (DESIGN.md Sec. 13).

``ShardedVikinBackend`` scales the single-device ``VikinBackend`` across a
device mesh: stack params are placed REPLICATED on a 1-D ("data",) serving
mesh (launch/mesh.serving_mesh) and each engine tick's active slots are
split into per-device request buckets run through one ``shard_map``-mapped
forward -- the engine drains its queue across N devices per tick while the
tick loop, slot lanes and admission logic stay exactly runtime/server.py.

The bucket contract is preserved PER SHARD: every device sees a zero-padded
power-of-two batch block (>= ``min_bucket``), so each shard executes the
same local program the single-device backend pins as bitwise-deterministic
(DESIGN.md Sec. 11 -- rows of a contraction are independent, so a request's
output does not depend on which bucket size, or now which shard, computed
it).  Multi-device serving is therefore bitwise identical to single-device
serving for the same requests (pinned in tests/test_sharded.py and gated by
the CI ``sharded-smoke`` job on forced host devices).

Simulated-hardware accounting swaps the single-chip report for the
multi-chip ``core/engine.VikinArray`` model: per-chip cycles for the row
shard each chip computes, plus the host scatter/gather transfer -- so
``ModePlan`` charges and per-request cycle attribution stay meaningful at
scale.

The mode-aware scheduler layer (runtime/scheduler.py) composes with this
backend unchanged: ``ShardedVikinBackend`` inherits the carry-over-aware
``batch_report(prev_mode=...)`` and the ``bucket``/``plan`` surface the
batch policies read, so ``--arch a,b,c --devices N`` wraps one sharded
backend per workload inside a MultiWorkloadBackend and mode-affinity
batching applies per tick exactly as on one device.

``ShardedVikinBackend`` is the DATA plan of the three array execution
plans (DESIGN.md Sec. 18); ``PipelineVikinBackend`` (layer stages across
chips) and ``HeteroVikinBackend`` (chips pinned per interconnect mode)
are the other two, and ``make_array_backend`` picks by plan name (the
``--array-plan`` flag of launch/serve).  All three serve BITWISE the same
outputs as the single-device ``VikinBackend``: the staged plans chain the
exact same per-layer math (``vikin_stack_apply(layer_range=...)`` slices)
over per-device param placements, and layer outputs do not depend on
which device, stage, or bucket computed them.
"""
from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine import VikinArray, VikinHW
from repro.core.modes import parse_mode
from repro.launch.mesh import require_devices, serving_mesh
from repro.runtime.backends import VikinBackend
from repro.utils import next_pow2 as _next_pow2


class ShardedVikinBackend(VikinBackend):
    """VikinBackend fanned out over ``devices`` data-parallel shards.

    Drop-in for ``VikinBackend`` in ``runtime/server.Engine``: only the
    batched forward (shard_map over the serving mesh), the bucket shape
    (``devices`` x per-shard power-of-two) and the cycle model (VikinArray)
    change; state staging, validation and slot handling are inherited.
    """

    def __init__(self, model: Any, params: Any, *, devices: int,
                 impl: str = "auto",
                 hw: Optional[VikinHW] = None, min_bucket: int = 2,
                 nnz_rates: Optional[Sequence[float]] = None,
                 masks: Any = None, array: Optional[VikinArray] = None,
                 precision: str = "f32", scales: Any = None) -> None:
        super().__init__(model, params, impl=impl, hw=hw,
                         min_bucket=min_bucket, nnz_rates=nnz_rates,
                         masks=masks, precision=precision, scales=scales)
        self.mesh = serving_mesh(devices)
        self.n_shards = devices
        self.array = array or VikinArray(hw=self.hw, n_chips=devices,
                                         precision=precision)
        if self.array.plan != "data":
            raise ValueError(
                f"ShardedVikinBackend is the 'data' array plan; a "
                f"{self.array.plan!r} array belongs to "
                "PipelineVikinBackend/HeteroVikinBackend "
                "(make_array_backend picks by plan)")
        if self.array.n_chips != devices:
            raise ValueError(
                f"array models {self.array.n_chips} chips but the mesh "
                f"shards over {devices} devices")
        if self.array.hw != self.hw:
            raise ValueError(
                "array.hw disagrees with the backend's hw: the array's "
                "chip model is what the cycle report runs")
        if self.array.precision != precision:
            raise ValueError(
                f"array precision {self.array.precision!r} disagrees with "
                f"the served precision {precision!r}")
        # replicated param placement: every shard owns a full copy of the
        # (tiny, KB-scale) stack; requests shard, weights don't.
        self.params = jax.device_put(
            self.params, NamedSharding(self.mesh, P()))
        fwd = jax.shard_map(
            self.forward_fn(),
            mesh=self.mesh,
            in_specs=(P(), P("data", None)),
            out_specs=P("data", None),
            check_vma=False,
        )
        self._fwd = jax.jit(fwd)

    def shard_bucket(self, n_active: int) -> int:
        """Per-shard rows: the power-of-two bucket for this shard's slice
        of the active set (>= min_bucket, the bitwise-determinism floor)."""
        per_shard = -(-max(n_active, 1) // self.n_shards)   # ceil div
        return _next_pow2(max(per_shard, self.min_bucket))

    def bucket(self, n_active: int) -> int:
        """Global batch fed to the mapped forward: ``n_shards`` contiguous
        per-shard buckets (shard j owns rows [j*b, (j+1)*b))."""
        return self.n_shards * self.shard_bucket(n_active)


class _StagedVikinBackend(VikinBackend):
    """Shared body of the layer-staged array plans (pipeline / hetero).

    Subclasses hand over ``_stage_ranges()`` -> [(lo, hi, device), ...]
    covering the stack in order; this base slices the (precision-converted)
    per-layer params onto each stage's device, jits ONE forward per stage
    (``vikin_stack_apply(layer_range=(lo, hi))`` -- the same layer math as
    the whole-stack jit, so outputs stay bitwise identical to the
    single-device backend), and chains them with an explicit activation
    device_put at every stage boundary (the hop the array model charges to
    the host port).

    The request bucket is inherited from ``VikinBackend`` (one power-of-two
    bucket; the full bucket flows through every stage), so slot handling,
    padding and validation are exactly the single-device backend's.
    """

    plan_name = "staged"

    def __init__(self, model: Any, params: Any, *, devices: int,
                 impl: str = "auto",
                 hw: Optional[VikinHW] = None, min_bucket: int = 2,
                 nnz_rates: Optional[Sequence[float]] = None,
                 masks: Any = None, array: Optional[VikinArray] = None,
                 precision: str = "f32", scales: Any = None) -> None:
        if precision == "int8":
            raise ValueError(
                f"the {self.plan_name!r} array plan serves f32/bf16 only: "
                "the int8 path quantizes and runs the stack as one unit "
                "(core/quant.quant_stack_apply), which staging would "
                "split; use the 'data' plan for int8 arrays")
        super().__init__(model, params, impl=impl, hw=hw,
                         min_bucket=min_bucket, nnz_rates=nnz_rates,
                         masks=masks, precision=precision, scales=scales)
        self.devices = require_devices(
            devices, f"--array-plan {self.plan_name}")
        self.n_devices = devices
        self.array = array or self._default_array()
        if self.array.plan != self.plan_name:
            raise ValueError(
                f"{type(self).__name__} runs the {self.plan_name!r} plan "
                f"but the array is configured for {self.array.plan!r}")
        if self.array.n_chips != devices:
            raise ValueError(
                f"array models {self.array.n_chips} chips but "
                f"{devices} devices were requested")
        if self.array.hw != self.hw:
            raise ValueError(
                "array.hw disagrees with the backend's hw: the array's "
                "chip model is what the cycle report runs")
        if self.array.precision != precision:
            raise ValueError(
                f"array precision {self.array.precision!r} disagrees with "
                f"the served precision {precision!r}")
        import jax.numpy as jnp
        from repro.models.ffn import vikin_stack_apply

        model_, impl_, masks_ = self.model, self.impl, self.masks
        self._stages = []
        for lo, hi, dev in self._stage_ranges():
            p_stage = jax.device_put(list(self.params[lo:hi]), dev)
            fn = jax.jit(
                lambda p, x, lo=lo, hi=hi: vikin_stack_apply(
                    p, x, model_, impl=impl_, masks=masks_,
                    layer_range=(lo, hi)))
            self._stages.append((fn, p_stage, dev))

        bf16 = self.precision == "bf16"

        def fwd(_params: Any, x: Any) -> Any:
            h = jnp.asarray(x)
            if bf16:
                h = h.astype(jnp.bfloat16)
            for fn, p_stage, dev in self._stages:
                h = fn(p_stage, jax.device_put(h, dev))
            return h.astype(jnp.float32) if bf16 else h

        self._fwd = fwd

    def _default_array(self) -> VikinArray:
        raise NotImplementedError

    def _stage_ranges(self) -> List[Tuple[int, int, Any]]:
        """[(lo, hi, device), ...] covering layers 0..n in order."""
        raise NotImplementedError


class PipelineVikinBackend(_StagedVikinBackend):
    """Pipeline-parallel array plan: one contiguous layer stage per chip.

    Execution chains the stages' jitted slices (bitwise == single-device);
    the CYCLE model (``VikinArray(plan="pipeline")``) is where the
    micro-batch overlap lives: steady-state issue at the slowest stage,
    fill/drain bubble, inter-stage activations over the shared host port,
    DMA setup per stage instead of per chip.  ``stage_map`` pins the
    layers-per-stage cut; default is an even split over
    ``min(devices, n_layers)`` chips.
    """

    plan_name = "pipeline"

    def __init__(self, model: Any, params: Any, *, devices: int,
                 stage_map: Optional[Sequence[int]] = None,
                 **kw: Any) -> None:
        self._stage_map = (tuple(int(n) for n in stage_map)
                           if stage_map is not None else None)
        super().__init__(model, params, devices=devices, **kw)

    def _default_array(self) -> VikinArray:
        return VikinArray(hw=self.hw, n_chips=self.n_devices,
                          precision=self.precision, plan="pipeline",
                          stage_map=self._stage_map)

    def _stage_ranges(self) -> List[Tuple[int, int, Any]]:
        sizes = self.array.stage_sizes(len(self.layers))
        out: List[Tuple[int, int, Any]] = []
        lo = 0
        for s, n in enumerate(sizes):
            out.append((lo, lo + n, self.devices[s]))
            lo += n
        return out


class HeteroVikinBackend(_StagedVikinBackend):
    """Heterogeneous mode-pinned array plan: chips never reconfigure.

    Each chip is pinned to ONE interconnect mode (``mode_pins``; default
    half pipeline-mode / half parallel-mode) and each maximal same-mode
    layer segment executes on its mode's pool -- so the stack's KAN
    segments only ever touch pipeline-pinned chips and its MLP segments
    parallel-pinned ones, and ``reconfig_cycles`` is identically 0 in the
    serving report whatever the request stream looks like.

    ``pinned_modes`` (a frozenset) is the scheduler contract
    (DESIGN.md Sec. 18): the engine forwards it via
    ``SchedContext.pinned_modes`` and mode-affinity scoring treats every
    pinned mode as free to enter, so a mixed KAN/MLP stream is served in
    arrival order with no mode-grouping delay AND no flips.
    """

    plan_name = "hetero"

    def __init__(self, model: Any, params: Any, *, devices: int,
                 mode_pins: Optional[Sequence] = None,
                 **kw: Any) -> None:
        self._mode_pins = (tuple(parse_mode(m) for m in mode_pins)
                           if mode_pins is not None else None)
        super().__init__(model, params, devices=devices, **kw)
        self.pinned_modes = frozenset(self.array.resolved_pins())
        # fail at construction, not first tick, when the stack needs a
        # mode no chip is pinned to
        for mode, _, _ in self.plan.segment_slices():
            if self.array.pool_size(mode) == 0:
                raise ValueError(
                    f"hetero array has no chip pinned to {mode.value!r} "
                    f"but {self.model.name!r} needs it (pins: "
                    f"{[m.value for m in self.array.resolved_pins()]})")

    def _default_array(self) -> VikinArray:
        return VikinArray(hw=self.hw, n_chips=self.n_devices,
                          precision=self.precision, plan="hetero",
                          mode_pins=self._mode_pins)

    def _stage_ranges(self) -> List[Tuple[int, int, Any]]:
        pins = self.array.resolved_pins()
        out: List[Tuple[int, int, Any]] = []
        for mode, lo, hi in self.plan.segment_slices():
            pool = [self.devices[i] for i, m in enumerate(pins)
                    if m is mode]
            if not pool:
                raise ValueError(
                    f"hetero array has no chip pinned to {mode.value!r} "
                    f"but the stack needs it")
            # the segment's batch runs on the pool's first chip; outputs
            # are row-independent, so WHERE rows run never changes them --
            # the pool row-split lives in the cycle model
            out.append((lo, hi, pool[0]))
        return out


def make_array_backend(model: Any, params: Any, *, devices: int,
                       plan: str = "data",
                       stage_map: Optional[Sequence[int]] = None,
                       mode_pins: Optional[Sequence] = None,
                       **kw: Any) -> Any:
    """Build the array backend for ``--array-plan`` (launch/serve).

    data -> ShardedVikinBackend (rows split, params replicated),
    pipeline -> PipelineVikinBackend (``stage_map`` = layers per stage),
    hetero -> HeteroVikinBackend (``mode_pins`` = one mode name per chip).
    """
    if plan == "data":
        if stage_map is not None or mode_pins is not None:
            raise ValueError(
                "stage_map/mode_pins only apply to the pipeline/hetero "
                "plans; the data plan replicates the whole stack")
        return ShardedVikinBackend(model, params, devices=devices, **kw)
    if plan == "pipeline":
        if mode_pins is not None:
            raise ValueError("mode_pins is a hetero-plan knob")
        return PipelineVikinBackend(model, params, devices=devices,
                                    stage_map=stage_map, **kw)
    if plan == "hetero":
        if stage_map is not None:
            raise ValueError("stage_map is a pipeline-plan knob")
        return HeteroVikinBackend(model, params, devices=devices,
                                  mode_pins=mode_pins, **kw)
    raise ValueError(
        f"unknown array plan {plan!r}; choose from data|pipeline|hetero")
