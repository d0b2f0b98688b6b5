#!/usr/bin/env python3
"""Serve the VIKIN stacks and the full-width KAN-FFN transformer on a TPU.

    python chip_smoke.py                # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips   # four chips: the array plans only

(a) vikin-kan2, vikin-mlp3 and vikin-mixed at their published widths,
    served together by one multi-workload Engine built as
    ``repro.launch.serve`` builds it (``--impl auto``: the compiled Pallas
    kernels), at f32, bf16 and int8.  Every output is compared with the
    same stack's jnp forward at matmul precision "highest" on the chip.
(b) qwen2-0.5b-kanffn at full scale (24 layers, d_model 896, KAN hidden
    1080) in f32 and bf16 through TransformerBackend: a few requests at
    two prompt lengths, a few decoded tokens each.  The first-step logits
    of the served backend are compared with the jnp forward, both at
    matmul precision "highest"; the served programs' own logits (default
    precision outside the kernels) are held to a looser bound.
--four-chips serves vikin-mixed at ``--devices 4`` under the data and the
    pipeline array plans and compares each, bitwise, with the same
    requests served on one device; it prints the device each shard or
    stage ran on.

Each phase's request set is served twice: the first pass compiles, the
second is warm, and ``compile_s`` is their difference.  A phase line holds
the device, ``compile_s``, requests served, wall seconds, the max error
with its bound, and whether every served program -- each bucket, each
prefill length, the decode step -- holds a Mosaic kernel
(``tpu_custom_call``).  The last line is the verdict as JSON.  The script
exits non-zero, without that line, when JAX finds no TPU or any phase
fails.  Everything runs in this one process, which owns the chips.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
PAPER = ("vikin-kan2", "vikin-mlp3", "vikin-mixed")
QWEN = "qwen2-0.5b-kanffn"

# Max |served - reference| allowed, as a fraction of 1 + max|reference|;
# beside each, the readings on a TPU v5e before these bounds were set
# (relative: 1 + max|reference| was 3.59 for the stacks).
# f32: the kernels contract f32 at full precision, so only the summation
# order differs (read 6.6e-8).  bf16: two output ulps, 2^-7 (read 1.6e-3;
# a one-ulp flip of the largest output is 2^-8).  int8: the kernels and
# the jnp forward share the quantized arithmetic and agree to f32
# rounding (read 5.0e-8); a requantization step away would exceed it.
BOUND = {"f32": 1e-6, "bf16": 2.0 ** -7, "int8": 1e-6}
# (b) compares like with like: the served backend's prefill and the jnp
# forward both at matmul precision "highest" (for bf16 the served program
# itself: bf16 operands contract exactly at any precision), so f32 is held
# near f32 rounding and a bf16 slip fails it (read: f32 4.4e-7, bf16
# 1.1e-2 relative).
QWEN_BOUND = {"f32": 1e-5, "bf16": 5e-2}
# The served programs themselves run the XLA matmuls outside the kernels
# (attention, embeddings, the MLP blocks) at the TPU's default precision,
# which rounds f32 operands to bf16: their logits are held to this bound
# (read 5.2e-3 for f32 and 1.1e-2 for bf16, relative).
SERVED_BOUND = 5e-2


class PhaseError(RuntimeError):
    pass


def _device():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _report(name, **fields):
    print(f"phase {name}: " + json.dumps({"device": _device(), **fields}),
          flush=True)


def _has_kernel(jitted, *args) -> bool:
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def _max_err(got, want):
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        raise PhaseError(f"served output shape {got.shape} (want "
                         f"{want.shape}) or non-finite values")
    return float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))


def _waves(models):
    """Request waves that make the engine form every bucket up to its
    slot count: 12 requests per workload round-robin, then one, then two
    per workload."""
    import numpy as np

    rng = np.random.default_rng(0)
    waves = []
    for per in (12, 1, 2):
        waves.append([(m.name, rng.random(m.sizes[0], dtype=np.float32))
                      for _ in range(per) for m in models])
    return waves


def _serve_waves(eng, waves):
    out = []
    t0 = time.perf_counter()
    for wave in waves:
        rids = [eng.submit(x, workload=w) for w, x in wave]
        res = eng.run_until_done()
        out.extend(res[r] for r in rids)
    return out, time.perf_counter() - t0


def paper_phase(serve, precision, archs=PAPER, scale="full", impl="auto"):
    """(a): the paper stacks through one multi-workload Engine."""
    import jax
    import numpy as np

    argv = ["--arch", ",".join(archs), "--scale", scale, "--precision",
            precision, "--slots", "8", "--impl", impl]
    args = serve.build_parser().parse_args(argv)
    eng, models, _ = serve.build_vikin_engine(
        args, [cfg for _, cfg in serve.resolve_archs(args.arch)])
    waves = _waves(models)
    cold, cold_s = _serve_waves(eng, waves)
    warm, warm_s = _serve_waves(eng, waves)
    if not all(np.array_equal(a, b) for a, b in zip(cold, warm)):
        raise PhaseError("a second pass over the same requests differs")

    backends = eng.backend.backends
    reqs = [r for wave in waves for r in wave]
    err, ref_max, kernel = 0.0, 0.0, True
    for name, b in backends.items():
        idx = [i for i, (w, _) in enumerate(reqs) if w == name]
        xs = np.stack([reqs[i][1] for i in idx])
        ref_b = copy.copy(b)
        ref_b.impl = "jnp"
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(ref_b.forward_fn())(b.params, xs))
        e, m = _max_err(np.stack([warm[i] for i in idx]), ref)
        err, ref_max = max(err, e), max(ref_max, m)
        for n in sorted({b.bucket(k) for k in range(1, eng.n_slots + 1)}):
            kernel &= _has_kernel(b._fwd, b.params,
                                  np.zeros((n, b.n_in), np.float32))
    bound = BOUND[precision] * (1.0 + ref_max)
    batches = {n: int(s.get("batches", 0))
               for n, s in eng.per_workload_stats().items()}
    _report(f"a/{precision}", archs=list(archs), compile_s=cold_s - warm_s,
            cold_s=cold_s, wall_s=warm_s, served=len(warm),
            batches=batches, max_err=err, bound=bound,
            tpu_custom_call=kernel)
    if err > bound:
        raise PhaseError(f"a/{precision}: max error {err} > bound {bound}")
    if not kernel:
        raise PhaseError(f"a/{precision}: served HLO has no Mosaic kernel")


def transformer_phase(serve, precision, arch=QWEN, scale="full",
                      impl="auto", prompt_lens=(16, 32)):
    """(b): the KAN-FFN transformer through TransformerBackend."""
    import jax
    import numpy as np

    from repro.runtime.backends import TransformerBackend

    argv = ["--arch", arch, "--scale", scale, "--precision", precision,
            "--slots", "4", "--max-len", "64", "--impl", impl]
    args = serve.build_parser().parse_args(argv)
    srv = serve.build_transformer_server(
        args, serve.resolve_archs(args.arch)[0][1])
    be = srv.backend
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, be.cfg.vocab_size, size=n).astype(np.int32)
               for n in (*prompt_lens, *prompt_lens)]

    def serve_once():
        t0 = time.perf_counter()
        rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
        out = srv.run_until_done()
        return [out[r] for r in rids], time.perf_counter() - t0

    cold, cold_s = serve_once()
    warm, warm_s = serve_once()
    if cold != warm:
        raise PhaseError("a second pass over the same requests differs")

    ref = TransformerBackend(be.cfg, be.params, impl="jnp",
                             precision=precision)
    ref.init_state(1, be.max_len)
    err, served_err, ref_max = 0.0, 0.0, 0.0
    for p, toks in zip(prompts, warm):
        served = np.asarray(be.prefill_logits(p[None, :])[0], np.float32)
        if int(np.argmax(served[0, -1])) != toks[0]:
            raise PhaseError("served first token is not its logits' argmax")
        with jax.default_matmul_precision("highest"):
            # bf16 operands contract exactly at any precision: the served
            # program is already like for like
            got = (served if precision == "bf16" else np.asarray(
                be.prefill_logits(p[None, :])[0], np.float32))
            want = np.asarray(ref.prefill_logits(p[None, :])[0], np.float32)
        e, m = _max_err(got, want)
        err, ref_max = max(err, e), max(ref_max, m)
        served_err = max(served_err, _max_err(served, want)[0])
    # every served program: each prefill length and the decode step
    kernel = all(_has_kernel(be._prefill_fn(n), be.params,
                             np.zeros((1, n), np.int32))
                 for n in prompt_lens)
    caches = be._T.init_caches(be.cfg, be.n_slots, be.max_len)
    kernel &= _has_kernel(be._decode, be.params,
                          np.zeros((be.n_slots, 1), np.int32), caches)
    bound = QWEN_BOUND[precision] * (1.0 + ref_max)
    served_bound = SERVED_BOUND * (1.0 + ref_max)
    _report(f"b/{precision}", arch=arch, layers=be.cfg.n_layers,
            d_model=be.cfg.d_model, prompt_lens=list(prompt_lens),
            compile_s=cold_s - warm_s, cold_s=cold_s, wall_s=warm_s,
            served=len(warm), tokens=sum(len(t) for t in warm),
            max_err=err, bound=bound, served_max_err=served_err,
            served_bound=served_bound, tpu_custom_call=kernel)
    if served_err > served_bound:
        raise PhaseError(f"b/{precision}: served logits' max error "
                         f"{served_err} > bound {served_bound}")
    if err > bound:
        raise PhaseError(f"b/{precision}: max error {err} > bound {bound}")
    if not kernel:
        raise PhaseError(f"b/{precision}: served HLO has no Mosaic kernel")


def _placement(backend, x):
    """Device ids each data shard or pipeline stage of ``backend`` ran on
    for the batch ``x``."""
    import jax

    if hasattr(backend, "_stages"):
        h, ids = jax.numpy.asarray(x), []
        for fn, p_stage, dev in backend._stages:
            h = fn(p_stage, jax.device_put(h, dev))
            ids.append(sorted(d.id for d in h.devices()))
        return {"stages": ids}
    y = backend._fwd(backend.params, x)
    return {"shards": sorted((s.device.id, s.index[0].start or 0)
                             for s in y.addressable_shards)}


def four_chip_phase(serve, plan, arch="vikin-mixed", devices=4):
    """``arch`` over ``devices`` chips under ``plan``, bitwise against
    the same requests on one device."""
    import numpy as np

    def engine(n):
        argv = ["--arch", arch, "--scale", "full", "--slots", "8",
                "--devices", str(n), "--array-plan",
                plan if n > 1 else "data"]
        args = serve.build_parser().parse_args(argv)
        return serve.build_vikin_engine(
            args, [cfg for _, cfg in serve.resolve_archs(args.arch)])

    eng_n, models, _ = engine(devices)
    eng_1, _, _ = engine(1)
    waves = [[(None, x) for _, x in wave] for wave in _waves(models)]
    cold, cold_s = _serve_waves(eng_n, waves)
    many, warm_s = _serve_waves(eng_n, waves)
    one, _ = _serve_waves(eng_1, waves)
    bitwise = all(np.array_equal(a, b) for a, b in zip(many, one))
    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(many, one))
    b = eng_n.backend
    x = np.zeros((b.bucket(8), b.n_in), np.float32)
    _report(f"four-chip/{plan}", arch=arch, devices=devices,
            compile_s=cold_s - warm_s, cold_s=cold_s, wall_s=warm_s,
            served=len(many), bitwise_equal_one_device=bitwise,
            max_abs_diff=diff, placement=_placement(b, x))
    if not bitwise:
        raise PhaseError(f"four-chip/{plan}: outputs differ from one "
                         f"device (max |diff| {diff})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve vikin-mixed over 4 chips (data and "
                         "pipeline plans) against one device, and nothing "
                         "else")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch import serve
        from repro.utils import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {dev})",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    try:
        if args.four_chips:
            if dev["count"] < 4:
                raise PhaseError(f"--four-chips needs 4 devices, found "
                                 f"{dev['count']}")
            for plan in ("data", "pipeline"):
                four_chip_phase(serve, plan)
        else:
            for precision in ("f32", "bf16", "int8"):
                paper_phase(serve, precision)
            for precision in ("f32", "bf16"):
                transformer_phase(serve, precision)
                gc.collect()
    except Exception:       # any failed phase fails the run, loudly
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
