"""Serving launcher: load (or init) a model and run the batched engine.

Transformer archs decode tokens over slot KV caches:

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --scale smoke --requests 6 --new-tokens 12

VIKIN archs (configs/vikin_models.VIKIN_ARCHS) serve stacked KAN/MLP
feed-forward workloads through the fused kernels, one inference per
request, and report simulated VIKIN cycles next to wall-clock:

  PYTHONPATH=src python -m repro.launch.serve --arch vikin-small \
      --requests 8 --slots 4 --impl pallas_interpret

A comma list of vikin archs serves SEVERAL workloads from one engine
process (runtime/backends.MultiWorkloadBackend) under a mode-aware batch
policy (runtime/scheduler.py, DESIGN.md Sec. 14): ``--policy
mode-affinity`` (default) groups same-ExecMode work so reconfiguration is
amortized across requests, ``--policy fifo`` is the strict arrival-order
baseline.  Requests are submitted round-robin across the archs -- the
adversarial interleaving for the reconfiguration schedule:

  PYTHONPATH=src python -m repro.launch.serve \
      --arch vikin-kan2,vikin-mlp3,vikin-mixed --policy mode-affinity \
      --requests 12 --slots 4 --impl pallas_interpret

``--ckpt`` points a vikin arch at a sparsified checkpoint produced by
``launch/train.py --arch vikin-*`` (params + calibrated two-stage masks,
DESIGN.md Sec. 12), so served outputs and simulated cycles reflect the
trained sparse model instead of random-init weights:

  PYTHONPATH=src python -m repro.launch.serve --arch vikin-small \
      --ckpt /tmp/vikin_ckpt --requests 8 --impl pallas_interpret

``--devices N`` serves the workload over an N-chip array; ``--array-plan``
picks how the stack maps onto the chips (DESIGN.md Sec. 13 + 18):
``data`` (default) splits request rows with replicated params
(runtime/sharded.ShardedVikinBackend), ``pipeline`` stages the layer
stack across chips (``--stage-map 2,1`` = layers per stage), ``hetero``
pins each chip to one interconnect mode (``--stage-map kan,kan,mlp,mlp``)
so reconfiguration cycles drop to 0.  Served outputs are bitwise
identical to ``--devices 1`` under EVERY plan.  On CPU, force the device
count before jax initializes:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --arch vikin-small \
      --devices 4 --array-plan pipeline --requests 8 \
      --impl pallas_interpret

``--trace`` replays a seeded arrival trace (runtime/loadgen.py) OPEN-loop
on the simulated clock -- arrivals land on the trace's schedule whether or
not the engine keeps up -- with ``--max-queue``/``--admission``/
``--drop-expired`` selecting the overload policy (DESIGN.md Sec. 15):

  PYTHONPATH=src python -m repro.runtime.loadgen --kind bursty \
      --arch vikin-small --load 2.0 --events 48 --deadline 0.0001 \
      --out /tmp/trace.json
  PYTHONPATH=src python -m repro.launch.serve --arch vikin-small \
      --trace /tmp/trace.json --max-queue 6 --admission shed \
      --drop-expired --slots 2 --impl pallas_interpret
"""
from __future__ import annotations

import argparse


def _split_stage_map(args):
    return [t.strip() for t in (args.stage_map or "").split(",")
            if t.strip()]


def _parse_stage_map(args):
    """--stage-map under --array-plan pipeline: layers per stage, e.g.
    '2,1' puts the first two layers on chip 0 and the last on chip 1."""
    toks = _split_stage_map(args)
    if args.array_plan != "pipeline" or not toks:
        return None
    try:
        return [int(t) for t in toks]
    except ValueError:
        raise SystemExit(
            f"--stage-map {args.stage_map!r}: the pipeline plan takes a "
            f"comma list of per-stage layer counts (e.g. 2,1)")


def _parse_mode_pins(args):
    """--stage-map under --array-plan hetero: one mode name per chip,
    e.g. 'kan,kan,mlp,mlp' (aliases: pipeline=kan, parallel=mlp)."""
    toks = _split_stage_map(args)
    if args.array_plan != "hetero" or not toks:
        return None
    return toks


def _make_vikin_backend(args, model):
    import jax

    from repro.models.ffn import vikin_stack_init
    from repro.runtime.backends import VikinBackend

    params = vikin_stack_init(jax.random.key(0), model)
    masks = None
    scales = None
    # accept --ckpt-dir too: train.py writes through that flag, and serving
    # random-init weights because the "wrong" spelling was used would be a
    # silently wrong benchmark
    ckpt = args.ckpt or args.ckpt_dir
    if ckpt:
        from repro.checkpoint import (
            restore_checkpoint,
            restore_masks,
            restore_scales,
        )
        # trained + sparsified checkpoint (launch/train.py --arch vikin-*):
        # params restored into the init tree's structure, masks bit-exact
        params, step, extra = restore_checkpoint(ckpt, params)
        masks = restore_masks(ckpt)
        scales = restore_scales(ckpt)
        print(f"restored {model.name} from {ckpt} step {step}")
        if extra:
            print(f"  trained on task={extra.get('task')} "
                  f"pattern_rate={extra.get('pattern_rate')} "
                  f"val_dense={extra.get('val_dense')} "
                  f"val_sparse={extra.get('val_sparse')}")
        if masks is not None:
            kept = [None if m is None else f"{m.n_keep}/{m.n}"
                    for m in masks]
            print(f"  restored per-layer masks (kept): {kept}")
        if args.precision == "int8" and scales is None:
            raise SystemExit(
                f"--precision int8 needs calibrated scales, but {ckpt} has "
                f"no scales.npz; re-export it with launch/train.py (scales "
                f"are always emitted alongside the masks)")
    elif args.precision == "int8":
        # no checkpoint: calibrate scales for the random-init stack from a
        # synthetic batch matching the features _serve_vikin submits
        import numpy as np
        from repro.core.calibrate import calibrate_scales
        rng = np.random.default_rng(0)
        calib_x = rng.random((256, model.sizes[0])).astype(np.float32)
        scales = calibrate_scales(params, model, calib_x, impl=args.impl)
        print(f"no checkpoint: calibrated int8 scales from a synthetic "
              f"batch (x={scales.summary()['x']})")
    if args.devices > 1:
        from repro.runtime.sharded import make_array_backend
        try:
            backend = make_array_backend(
                model, params, impl=args.impl, masks=masks,
                devices=args.devices, plan=args.array_plan,
                stage_map=_parse_stage_map(args),
                mode_pins=_parse_mode_pins(args),
                precision=args.precision, scales=scales)
        except ValueError as e:
            raise SystemExit(str(e))
        if args.array_plan == "data":
            print(f"sharded serving: {args.devices} devices "
                  f"({backend.mesh.devices.ravel()[0].platform}), "
                  f"per-shard bucket >= {backend.shard_bucket(args.slots)} "
                  f"at full occupancy")
        elif args.array_plan == "pipeline":
            stages = [(lo, hi) for lo, hi, _ in backend._stage_ranges()]
            print(f"pipeline serving: {args.devices} chips, "
                  f"{len(stages)} layer stages {stages}")
        else:
            pins = [m.value for m in backend.array.resolved_pins()]
            print(f"hetero serving: {args.devices} chips pinned {pins} "
                  f"(reconfig cycles pinned to 0)")
    else:
        backend = VikinBackend(model, params, impl=args.impl, masks=masks,
                               precision=args.precision, scales=scales)
    if args.precision != "f32":
        print(f"serving precision: {args.precision} "
              f"(f32 accumulation, dtype-aware DMA model)")
    plan = backend.plan.summary()
    print(f"arch {model.name}: layers={list(model.layer_kinds)} "
          f"sizes={list(model.sizes)} pattern_rate={model.pattern_rate}")
    print(f"mode plan: {plan['segments']} "
          f"({plan['n_switches']} switches, "
          f"{plan['reconfig_cycles']} reconfig cycles/inference)")
    return backend


def build_vikin_engine(args, models):
    """The Engine ``--arch vikin-*`` serves from: one backend per model
    (a MultiWorkloadBackend over several), built as the flags say.
    Returns (engine, models as served, multi-workload?)."""
    from repro.runtime.backends import MultiWorkloadBackend
    from repro.runtime.server import Engine

    models = [m.reduce() if args.scale == "smoke" else m for m in models]
    if args.array_plan != "data" and args.devices <= 1:
        raise SystemExit(
            f"--array-plan {args.array_plan} needs a multi-chip array; "
            f"pass --devices N (N > 1) and, on CPU, "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    if args.stage_map and args.array_plan == "data":
        raise SystemExit(
            "--stage-map only applies to --array-plan pipeline (layers "
            "per stage) or hetero (mode pins per chip)")
    multi = len(models) > 1
    if multi and (args.ckpt or args.ckpt_dir):
        raise SystemExit(
            "--ckpt restores ONE trained model; serve a single --arch with "
            "it (a multi-workload engine would silently pair the "
            "checkpoint with every arch)")
    backends = {m.name: _make_vikin_backend(args, m) for m in models}
    if multi:
        backend = MultiWorkloadBackend(backends)
        print(f"multi-workload scheduler: {sorted(backends)} "
              f"under policy {args.policy!r}")
    else:
        backend = next(iter(backends.values()))
    try:
        eng = Engine(backend, n_slots=args.slots, policy=args.policy,
                     max_queue=args.max_queue, admission=args.admission,
                     drop_expired=args.drop_expired)
    except ValueError as e:
        raise SystemExit(str(e))
    if eng.max_queue is not None:
        print(f"admission control: policy {eng.admission!r}, "
              f"max_queue {eng.max_queue} per workload"
              + (", expired queued requests dropped" if eng.drop_expired
                 else ""))
    return eng, models, multi


def _serve_vikin(args, models):
    import numpy as np

    eng, models, multi = build_vikin_engine(args, models)
    if args.trace:
        return _replay_trace(args, eng)

    rng = np.random.default_rng(0)
    rids = {}
    # interleave the workloads round-robin: the adversarial arrival order
    # for the mode-affinity policy to untangle
    for i in range(args.requests):
        m = models[i % len(models)]
        rids[eng.submit(rng.random(m.sizes[0], dtype=np.float32),
                        workload=m.name if multi else None)] = m.name
    out = eng.run_until_done()
    for rid in sorted(out):
        y = out[rid]
        print(f"req {rid} [{rids[rid]}]: out[{y.shape[0]}] "
              f"mean={float(y.mean()):+.4f}")

    s, tp = eng.stats, eng.throughput()
    print(f"\n{int(s['served'])} requests in {int(s['ticks'])} batches "
          f"(policy {eng.policy.name}): "
          f"wall {s['wall_s']*1e3:.1f} ms ({tp.get('wall_rps', 0):.1f} req/s)")
    print(f"simulated VIKIN: {s['sim_cycles']:.0f} cycles, "
          f"{s['sim_latency_s']*1e6:.1f} us "
          f"({tp.get('sim_rps', 0):.0f} req/s), "
          f"{int(s['mode_switches'])} mode switches "
          f"({s['reconfig_cycles']:.0f} reconfig cycles)")
    print(f"latency: queue-wait p50 {s.get('p50_queue_wait_wall_s', 0)*1e3:.2f} ms "
          f"/ p95 {s.get('p95_queue_wait_wall_s', 0)*1e3:.2f} ms wall, "
          f"p95 {s.get('p95_queue_wait_sim_s', 0)*1e6:.1f} us sim; "
          f"service p95 {s.get('p95_service_wall_s', 0)*1e3:.2f} ms wall")
    for name, ws in sorted(eng.per_workload_stats().items()):
        print(f"  workload {name}: {int(ws.get('served', 0))} served in "
              f"{int(ws.get('batches', 0))} batches, "
              f"{ws.get('sim_cycles', 0):.0f} sim cycles, "
              f"{ws.get('reconfig_cycles', 0):.0f} reconfig cycles")
    if "chip_cycles" in s:
        print(f"  array: {args.devices} chips, "
              f"{s['chip_cycles']:.0f} per-chip compute cycles + "
              f"{s['comm_cycles']:.0f} scatter/gather cycles")


def _replay_trace(args, eng):
    """Open-loop replay of a trace file (runtime/loadgen.py) on the
    deterministic simulated clock: arrivals land on the trace's schedule
    whether or not the engine keeps up, so this is the overload /
    load-testing entry point (DESIGN.md Sec. 15)."""
    from repro.runtime.loadgen import Trace, replay

    trace = Trace.load(args.trace)
    print(f"replaying {args.trace}: {len(trace.events)} arrivals over "
          f"{trace.horizon_s*1e3:.3f} ms ({trace.offered_rps():.0f} req/s "
          f"offered), sha256 {trace.sha256()[:16]}...")
    rep = replay(eng, trace, mode="sim")
    print(f"\noffered {rep['offered']} -> submitted {rep['submitted']}, "
          f"completed {rep['completed']} "
          f"(rejected {rep['rejected']}, shed {rep['shed']}, "
          f"expired {rep['expired']})")
    met = rep["deadline_met"]
    print(f"throughput: offered {rep['offered_rps']:.0f} req/s, achieved "
          f"{rep['achieved_rps']:.0f} req/s, goodput "
          f"{rep['goodput_rps']:.0f} req/s"
          + (f" ({met}/{rep['completed']} met deadline, "
             f"{rep['deadline_misses']} misses)" if met is not None else ""))
    print(f"end-to-end latency (sim): p50 {rep['p50_latency_s']*1e6:.1f} / "
          f"p95 {rep['p95_latency_s']*1e6:.1f} / "
          f"p99 {rep['p99_latency_s']*1e6:.1f} us")
    print(f"queue depth high-water mark: {rep['queue_depth_hwm']}"
          + (f" (bound {eng.max_queue} "
             f"{'respected' if rep['bound_respected'] else 'EXCEEDED'})"
             if eng.max_queue is not None else " (unbounded)"))
    ov = eng.overload_stats()
    for kind in ("rejected", "shed", "expired"):
        if eng.stats[kind]:
            print(f"  {kind}: by_workload={ov[kind]['by_workload']} "
                  f"by_priority={ov[kind]['by_priority']}")
    if rep["incomplete"]:
        print("WARNING: replay ended with work still in flight "
              "(max_ticks or stalled admission)")


def build_transformer_server(args, cfg):
    """The Server a transformer ``--arch`` serves from, built as the flags
    say (random init from key 0 unless ``--ckpt-dir`` restores params)."""
    import jax

    from repro.checkpoint import latest_step, restore_checkpoint
    from repro.models import transformer as T
    from repro.runtime.server import Server

    if cfg.enc_dec or cfg.frontend is not None:
        raise SystemExit(
            f"arch {cfg.name!r} ({cfg.family}) needs modality inputs "
            f"(frames/patches) that the token-only serving path does not "
            f"provide; serve a decoder-only arch or a vikin-* workload")
    if args.scale == "smoke":
        cfg = cfg.reduce()
    params = T.init_params(jax.random.key(0), cfg)
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state = {"params": params}
        restored, step, _ = restore_checkpoint(args.ckpt_dir, state)
        params = restored["params"]
        print(f"restored params from step {step}")

    kanffn = cfg.ffn_kinds is not None
    srv = Server(cfg, params, n_slots=args.slots, max_len=args.max_len,
                 impl=args.impl if kanffn and args.impl != "auto" else None,
                 precision=args.precision)
    if kanffn:
        plan = srv.backend.plan.summary()
        print(f"arch {cfg.name}: kan-ffn hybrid, ffn_kinds="
              f"{list(cfg.ffn_kinds)} impl={srv.backend.cfg.ffn_impl} "
              f"precision={args.precision}")
        print(f"mode plan: {plan['segments']} "
              f"({plan['n_switches']} switches, "
              f"{plan['reconfig_cycles']} reconfig cycles/instance)")
    return srv


def _serve_transformer(args, cfg):
    import numpy as np

    srv = build_transformer_server(args, cfg)
    kanffn = cfg.ffn_kinds is not None
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        n = int(rng.integers(3, 16))
        srv.submit(rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
                   max_new_tokens=args.new_tokens)
    out = srv.run_until_done()
    for rid, toks in sorted(out.items()):
        print(f"req {rid}: {toks}")
    s = srv.stats
    print(f"\n{int(s['served'])} requests, {int(s['ticks'])} ticks, "
          f"wall {s['wall_s']:.2f} s")
    if kanffn:
        print(f"simulated VIKIN: {s['sim_cycles']:.0f} cycles, "
              f"{s['sim_latency_s']*1e6:.1f} us, "
              f"{int(s['mode_switches'])} mode switches "
              f"({s['reconfig_cycles']:.0f} reconfig cycles)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="one arch id, or a comma list of vikin-* archs "
                         "served together by the multi-workload scheduler "
                         "(e.g. vikin-kan2,vikin-mlp3,vikin-mixed)")
    ap.add_argument("--policy", default="mode-affinity",
                    choices=["fifo", "mode-affinity"],
                    help="batch-formation policy (runtime/scheduler.py); "
                         "fifo is the bit-compatible arrival-order "
                         "baseline")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--ckpt-dir", default=None,
                    help="transformer archs: restore params from here")
    ap.add_argument("--ckpt", default=None,
                    help="vikin archs: sparsified checkpoint dir from "
                         "launch/train.py (params + masks)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "jnp", "pallas", "pallas_interpret"],
                    help="kernel dispatch for vikin-* and kan-ffn archs")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="vikin archs: served precision (DESIGN.md Sec. "
                         "16); int8 needs the checkpoint's calibrated "
                         "scales and dequantizes into f32 accumulation")
    ap.add_argument("--devices", type=int, default=1,
                    help="vikin archs: array serving over N devices "
                         "(runtime/sharded; outputs bitwise identical to "
                         "--devices 1 under every --array-plan)")
    ap.add_argument("--array-plan", default="data",
                    choices=["data", "pipeline", "hetero"],
                    help="how the array maps the stack onto --devices "
                         "chips (DESIGN.md Sec. 18): data = rows split / "
                         "params replicated; pipeline = layer stages with "
                         "micro-batch overlap; hetero = chips pinned per "
                         "interconnect mode (reconfig cycles -> 0)")
    ap.add_argument("--stage-map", default=None,
                    help="plan-specific chip map: pipeline takes layers "
                         "per stage ('2,1'); hetero takes one mode per "
                         "chip ('kan,kan,mlp,mlp')")
    ap.add_argument("--trace", default=None,
                    help="vikin archs: replay this arrival-trace JSON "
                         "(python -m repro.runtime.loadgen) OPEN-loop on "
                         "the simulated clock instead of a closed burst")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound each workload queue at N pending requests "
                         "(admission control, DESIGN.md Sec. 15)")
    ap.add_argument("--admission", default="unbounded",
                    choices=["unbounded", "reject", "shed"],
                    help="full-queue policy: reject the newcomer, or shed "
                         "the lowest-priority queued request (needs "
                         "--max-queue)")
    ap.add_argument("--drop-expired", action="store_true",
                    help="shed queued requests whose deadline already "
                         "passed instead of serving them dead")
    return ap


def resolve_archs(arch: str):
    """``--arch`` -> [(family, config), ...], with the launcher's rules on
    which families may be served together."""
    from repro.configs.registry import get_serving_config

    names = [a.strip() for a in arch.split(",") if a.strip()]
    if not names:
        raise SystemExit("--arch got no arch ids; pass one id or a comma "
                         "list like vikin-kan2,vikin-mlp3")
    try:
        resolved = [get_serving_config(n) for n in names]
    except KeyError as e:
        raise SystemExit(str(e.args[0]))
    families = {fam for fam, _ in resolved}
    if len(names) > 1 and families != {"vikin"}:
        raise SystemExit(
            f"multi-workload serving (--arch a,b,c) is vikin-only "
            f"(runtime/scheduler.py); got families {sorted(families)}. "
            f"Serve one transformer arch at a time")
    return resolved


def main():
    args = build_parser().parse_args()

    from repro.utils import enable_compile_cache

    enable_compile_cache()
    resolved = resolve_archs(args.arch)
    families = {fam for fam, _ in resolved}
    if families == {"vikin"}:
        _serve_vikin(args, [cfg for _, cfg in resolved])
    else:
        if args.devices > 1:
            raise SystemExit(
                f"--devices is vikin-only (runtime/sharded); serving "
                f"{args.arch!r} would silently run single-device. Drop "
                f"the flag or serve a vikin-* workload")
        if args.array_plan != "data" or args.stage_map:
            raise SystemExit(
                "--array-plan/--stage-map are vikin-only (runtime/"
                "sharded); serve a vikin-* workload")
        if args.trace:
            raise SystemExit(
                f"--trace is vikin-only (runtime/loadgen replays on the "
                f"simulated VIKIN clock); {args.arch!r} has no simulated "
                f"cycle model to replay against")
        if args.max_queue is not None or args.admission != "unbounded":
            raise SystemExit(
                "--max-queue/--admission are vikin-only here; the "
                "transformer Server keeps the unbounded back-compat path")
        cfg = resolved[0][1]
        if args.precision != "f32":
            # kan-ffn transformers serve bf16 through the same backend
            # cast path as vikin; int8 stays vikin-only (core/quant)
            if cfg.ffn_kinds is None:
                raise SystemExit(
                    f"--precision is vikin/kan-ffn-only; plain arch "
                    f"{args.arch!r} serves its configured dtype "
                    f"({cfg.dtype})")
            if args.precision == "int8":
                raise SystemExit(
                    "--precision int8 is vikin-only (core/quant path); "
                    "kan-ffn transformers serve f32 or bf16")
        _serve_transformer(args, cfg)


if __name__ == "__main__":
    main()
