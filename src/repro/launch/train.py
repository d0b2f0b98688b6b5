"""Production training launcher.

On a real cluster every host runs this under its TPU runtime and
jax.distributed wires the mesh; in this container it runs the same code on
the host mesh.  ``--dry-run`` lowers/compiles for the production mesh
instead of executing (see dryrun.py for the full sweep driver).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --steps 100 --seq 128 --batch 8 --scale smoke
  PYTHONPATH=src python -m repro.launch.train --arch granite-20b \
      --shape train_4k --dry-run

``--arch vikin-*`` instead runs the paper pipeline: train a KAN/MLP stack
dense, calibrate two-stage sparsity masks post-training, and export a
sparsified checkpoint (params + masks) that launch/serve.py --ckpt serves
(DESIGN.md Sec. 12):

  PYTHONPATH=src python -m repro.launch.train --arch vikin-small \
      --steps 200 --pattern 0.5 --ckpt-dir /tmp/vikin_ckpt
"""
from __future__ import annotations

import argparse
import os
import tempfile


def _train_vikin(args, model):
    """Train -> calibrate -> sparsified checkpoint for a VIKIN stack."""
    from repro.checkpoint import save_checkpoint
    from repro.core.calibrate import (
        calibrate_scales,
        calibrate_stack,
        keep_per_group_for_rate,
        masked_pattern_rates,
    )
    from repro.core.engine import run_model
    from repro.data.stack_task import task_for_model
    from repro.runtime.trainer import StackTrainer, StackTrainerConfig

    data = task_for_model(model, classify=(args.loss == "xent"),
                          seed=args.seed)
    tcfg = StackTrainerConfig(
        steps=args.steps, batch_size=args.batch, lr=args.lr,
        impl=args.impl, loss=args.loss, seed=args.seed,
        log_every=max(1, args.steps // 5))
    trainer = StackTrainer(model, data, tcfg)
    print(f"arch {model.name}: layers={list(model.layer_kinds)} "
          f"sizes={list(model.sizes)} task={data['task']} "
          f"({data['train_x'].shape[0]} train samples)")
    out = trainer.run()

    # post-training calibration at the deployment rate (Table II style):
    # --pattern overrides; 0 falls back to the arch's configured rate
    rate = args.pattern if args.pattern > 0 else model.pattern_rate
    kpg = keep_per_group_for_rate(rate)
    calib_x = data["train_x"][:args.calib_samples]
    sp = calibrate_stack(out["params"], model, calib_x,
                         keep_per_group=kpg, impl=args.impl)
    # quantization scales from the SAME calibration batch: always emitted,
    # so any checkpoint can later be served at --precision int8
    scales = calibrate_scales(out["params"], model, calib_x, impl=args.impl)
    # run() already evaluated the final dense params; only sparse is new
    dense_eval = {k: v for k, v in out.items() if k.startswith("val_")}
    sparse_eval = trainer.evaluate(masks=sp.masks)
    rates = masked_pattern_rates(sp.masks)
    dense_rep = run_model(model.layer_works(
        pattern_rates=[0.0] * model.n_layers))
    sparse_rep = run_model(model.layer_works(pattern_rates=rates))

    extra = {
        "arch": model.name, "task": data["task"], "loss": args.loss,
        "pattern_rate": rate, "seed": args.seed,
        "mask_keep_rates": sp.summary()["keep_rates"],
        "val_dense": dense_eval, "val_sparse": sparse_eval,
        "sim_cycles_dense": dense_rep.cycles,
        "sim_cycles_sparse": sparse_rep.cycles,
        "precision": args.precision,
        "scale_x": scales.summary()["x"],
    }
    masks = (sp.masks if any(m is not None for m in sp.masks) else None)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(
        prefix=f"vikin_{model.name}_")
    path = save_checkpoint(ckpt_dir, args.steps, out["params"],
                           extra=extra, masks=masks, scales=scales)
    speedup = dense_rep.cycles / max(sparse_rep.cycles, 1.0)
    print(f"calibrated masks at rate {rate}: keep_rates="
          f"{sp.summary()['keep_rates']}")
    if args.precision == "int8":
        from repro.core.quant import quant_stack_apply, quantize_stack_params
        import jax.numpy as jnp
        import numpy as np
        qp = quantize_stack_params(out["params"], model, scales)
        yq = np.asarray(quant_stack_apply(
            qp, jnp.asarray(data["val_x"]), model, scales,
            impl=args.impl, masks=list(sp.masks)))
        mse_q = float(np.mean((yq - np.asarray(data["val_y"])) ** 2))
        print(f"val int8-sparse mse {mse_q:.6f} "
              f"(scales x={extra['scale_x']})")
    print(f"val dense {dense_eval} -> sparse {sparse_eval}")
    print(f"simulated cycles dense {dense_rep.cycles:.0f} -> sparse "
          f"{sparse_rep.cycles:.0f} ({speedup:.2f}x)")
    print(f"sparsified checkpoint: {path} (masks + int8 scales)")
    print(f"serve it:  PYTHONPATH=src python -m repro.launch.serve "
          f"--arch {model.name} --ckpt {ckpt_dir}"
          + (" --precision int8" if args.precision == "int8" else ""))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--scale", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=None,
                    help="default: 3e-4 (transformer) / 1e-2 (vikin stacks)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ffn", default=None)
    ap.add_argument("--pattern", type=float, default=0.0,
                    help="stage-2 sparsity rate (vikin: calibration rate; "
                         "0 uses the arch's configured rate)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--loss", default="mse", choices=["mse", "xent"],
                    help="vikin stack task: regression | classification")
    ap.add_argument("--impl", default="jnp",
                    choices=["auto", "jnp", "pallas", "pallas_interpret"],
                    help="kernel dispatch for vikin-* training")
    ap.add_argument("--calib-samples", type=int, default=256,
                    help="calibration batch size for mask derivation")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="vikin: target serving precision; int8 scales are "
                         "always calibrated + checkpointed, int8 here also "
                         "prints the quantized val accuracy")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()

    from repro.configs.vikin_models import VIKIN_ARCHS
    from repro.utils import enable_compile_cache

    enable_compile_cache()

    if args.arch in VIKIN_ARCHS:
        if args.lr is None:
            args.lr = 1e-2
        return _train_vikin(args, VIKIN_ARCHS[args.arch])
    if args.lr is None:
        args.lr = 3e-4

    if args.dry_run:
        # re-exec through dryrun so XLA_FLAGS is set before jax imports
        os.execvp("python", ["python", "-m", "repro.launch.dryrun",
                             "--arch", args.arch, "--shape", args.shape,
                             "--mesh", "both"])

    import dataclasses
    from repro.configs.registry import get_config
    from repro.data.lm import LMDataConfig, SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import StepOptions
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.scale == "smoke":
        cfg = cfg.reduce()
    over = {}
    if args.ffn:
        over["ffn_kind"] = args.ffn
    if args.pattern:
        over["pattern_rate"] = args.pattern
    if over:
        cfg = dataclasses.replace(cfg, **over)

    data = SyntheticLM(LMDataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch))
    tcfg = TrainerConfig(
        max_steps=args.steps,
        ckpt_dir=args.ckpt_dir or tempfile.mkdtemp(prefix="train_"),
        ckpt_every=max(10, args.steps // 5), log_every=10)
    trainer = Trainer(cfg, tcfg, make_host_mesh(), data,
                      StepOptions(lr=args.lr, total_steps=args.steps,
                                  warmup=min(100, args.steps // 10)))
    out = trainer.run_with_restarts()
    print(f"final step {out['final_step']}, "
          f"loss {out['metrics'][-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
