"""The paper's KAN/MLP stacks behind one multi-workload Engine.

The timed path is the program's own: ``Engine.tick`` ->
``MultiWorkloadBackend`` -> ``VikinBackend.step`` -> ``vikin_stack_apply``
(``impl`` from the configuration; "auto" is the compiled Pallas kernels
on a TPU).  The benchmark makes the weights and the request payloads from
the seed; the check compares every output the window returned with the
plain reference (reference/stacks.py) run on the same weights.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chip import counts, traffic, weights
from chip.harness import Probe, SetupError
from chip.reference.stacks import stack_forward

POOL = 65536          # distinct payloads; request i sends row i % POOL
REF_BLOCK = 8192      # reference rows per call


def expand_keep(spec: Optional[Dict], n: int) -> List[int]:
    """Kept indices of an m-of-4 tile over ``n`` (the configuration's
    ``keep_rule``): the tile repeats, a trailing partial group is kept
    whole; no tile keeps everything."""
    if spec is None:
        return list(range(n))
    tile = spec["tile"]
    full = (n // len(tile)) * len(tile)
    return [i for i in range(n) if i >= full or tile[i % len(tile)]]


def model_layers(model: Dict) -> List[Dict]:
    """Per-layer shapes with their kept indices expanded."""
    n_bases = model.get("grid", 0) + model.get("order", 0)
    expanded = dict(model)
    expanded["keep"] = [
        expand_keep(spec, n_bases if kind == "kan" else model["sizes"][i])
        for i, (kind, spec) in enumerate(zip(model["kinds"], model["keep"]))]
    return counts.stack_layers(expanded)


def weight_specs(model: Dict) -> List[List]:
    """(name, shape, mean, std) per leaf of each layer, in the program's
    parameter layout (w_b/t for KAN layers, w/b for MLP layers)."""
    out = []
    for layer in model_layers(model):
        a, b = layer["n_in"], layer["n_out"]
        if layer["kind"] == "kan":
            nb = model["grid"] + model["order"]
            kept = len(layer["basis_keep"])
            out.append([("w_b", (a, b), 0.0, 1.0 / np.sqrt(a)),
                        ("t", (a, nb, b), 0.0, 1.0 / np.sqrt(a * kept))])
        else:
            out.append([("w", (a, b), 0.0, np.sqrt(2.0 / a)),
                        ("b", (b,), 0.0, 0.1)])
    return out


class System:
    def __init__(self, config: Dict, cell: Dict, seed: int) -> None:
        from repro.configs.vikin_models import VIKIN_ARCHS
        from repro.runtime.backends import MultiWorkloadBackend, VikinBackend
        from repro.runtime.server import Engine

        if config["dtype"] != "float32":
            raise SetupError("vikin_stacks serves float32 configurations")
        self.config, self.cell, self.seed = config, cell, seed
        self.names = [w for w, _ in cell["mix"]]
        self.models = config["models"]
        self.layers = {n: model_layers(self.models[n]) for n in self.names}
        for n in self.names:
            self._match(VIKIN_ARCHS[n], self.models[n])
        specs, owner = [], []
        for n in self.names:
            for li, leaves in enumerate(weight_specs(self.models[n])):
                for leaf, shape, mean, std in leaves:
                    specs.append((shape, jnp.float32, mean, std))
                    owner.append((n, li, leaf))
        arrays = weights.make(specs, seed)
        self.params: Dict[str, List[Dict]] = {
            n: [{} for _ in self.layers[n]] for n in self.names}
        for (n, li, leaf), arr in zip(owner, arrays):
            self.params[n][li][leaf] = arr
        jax.block_until_ready(arrays)
        self.backends = {n: VikinBackend(VIKIN_ARCHS[n], self.params[n],
                                         impl=config["impl"])
                         for n in self.names}
        self.probe = Probe(MultiWorkloadBackend(self.backends))
        self.engine = Engine(self.probe, n_slots=config["slots"],
                             policy=config["policy"], admission="unbounded",
                             drop_expired=False)
        rng = traffic.rng_for(seed, 3)
        n_in = {self.models[n]["sizes"][0] for n in self.names}
        if len(n_in) != 1:
            raise SetupError("the stacks of one cell share an input width")
        self.pool = rng.uniform(-1.0, 1.0, (POOL, n_in.pop())
                                ).astype(np.float32)
        self.stream = traffic.Stream(cell, seed)
        self.arrivals = None

    @staticmethod
    def _match(arch, model: Dict) -> None:
        """The program's model must be the configuration's."""
        got = (tuple(arch.sizes), tuple(arch.layer_kinds),
               float(arch.pattern_rate))
        want = (tuple(model["sizes"]), tuple(model["kinds"]),
                float(model["pattern_rate"]))
        if got != want or (model.get("grid") is not None and (
                arch.grid, arch.order) != (model["grid"], model["order"])):
            raise SetupError(f"{arch.name}: program has {got}, the "
                             f"configuration states {want}")

    # ------------------------------------------------------------ window
    def prepare(self, seconds: float) -> None:
        if self.cell["loop"] == "open":
            self.arrivals = traffic.arrival_times(self.cell["arrivals"],
                                                  seconds, self.seed)
            self.stream = traffic.Stream(self.cell, self.seed,
                                         block=len(self.arrivals))

    def warmup(self) -> None:
        """Compile the buckets this cell's engine forms (2, 4 and 8 of
        each stack), then serve a short untimed burst through the engine
        so its host paths are warm."""
        for b in self.backends.values():
            for k in self.config["buckets"]:
                b.warmup(k)
        rng = traffic.rng_for(self.seed, 4)
        for _ in range(2):
            for i in range(4 * self.config["slots"]):
                name = self.names[i % len(self.names)]
                self.engine.submit(self.pool[rng.integers(POOL)],
                                   workload=name)
            self.engine.run_until_done(max_ticks=10_000)
        self.probe.spans.clear()
        self.probe.touched.clear()

    def submit(self, index: int, start: float) -> int:
        w = self.names[self.stream.workload(index)]
        return self.engine.submit(self.pool[index % POOL], workload=w,
                                  t_submit=start)

    def release(self) -> None:
        """Drop the engine and its device state before the check."""
        self.engine = self.probe = self.backends = None

    # ------------------------------------------------------------- check
    def reference(self, mode: str = "highest") -> Dict[str, np.ndarray]:
        """The reference's output for every payload, per stack."""
        out = {}
        for n in self.names:
            m = self.models[n]
            fwd = jax.jit(lambda p, x, layers=self.layers[n], m=m:
                          stack_forward(p, x, layers, self.config["spline"],
                                        m.get("grid", 0), m.get("order", 0),
                                        mode))
            out[n] = np.concatenate([
                np.asarray(fwd(self.params[n], self.pool[s:s + REF_BLOCK]))
                for s in range(0, POOL, REF_BLOCK)])
        return out

    def compare(self, records, ref: Dict[str, np.ndarray]) -> Dict:
        """Every returned output against the reference: the largest error
        relative to 1 + max|reference|, and the requests whose error
        exceeds the limit."""
        limit = self.config["check"]["limit"]
        scale = 1.0 + max(float(np.max(np.abs(r))) for r in ref.values())
        worst, failed = 0.0, 0
        for n_idx, n in enumerate(self.names):
            recs = [r for r in records if r.req is not None
                    and self.stream.workload(r.index) == n_idx]
            if not recs:
                continue
            want = ref[n][np.asarray([r.index % POOL for r in recs])]
            got = np.stack([np.asarray(r.req.output, np.float64).reshape(-1)
                            if np.shape(r.req.output) == want.shape[1:]
                            else np.full(want.shape[1], np.nan)
                            for r in recs])
            err = np.max(np.abs(got - want), axis=1) / scale
            err = np.where(np.isfinite(err), err, np.inf)
            worst = max(worst, float(np.max(err)))
            if limit is not None:
                failed += int(np.sum(err > limit))
        return {"checks": {"stack_err": {"value": worst, "limit": limit}},
                "failed": failed, "compared": sum(
                    1 for r in records if r.req is not None)}

    def check(self, records) -> Dict:
        t = time.perf_counter()
        out = self.compare(records, self.reference())
        out["seconds"] = time.perf_counter() - t
        return out

    def readings(self, records) -> Dict[str, float]:
        """The number compared, for the program and for the control (the
        reference at the configuration's control precision put in the
        program's place), over the same returned requests."""
        ref = self.reference()
        ctrl = self.reference(self.config["check"]["control"])
        scale = 1.0 + max(float(np.max(np.abs(r))) for r in ref.values())
        control = 0.0
        for n_idx, n in enumerate(self.names):
            rows = np.unique([r.index % POOL for r in records
                              if r.req is not None
                              and self.stream.workload(r.index) == n_idx])
            if len(rows):
                control = max(control, float(np.max(
                    np.abs(ctrl[n][rows] - ref[n][rows]))) / scale)
        program = self.compare(records, ref)["checks"]["stack_err"]["value"]
        return {"program": program, "control": control}

    # ----------------------------------------------------------- counts
    def step_flops(self, info) -> float:
        """Model FLOPs of one backend step (``info``: the active requests'
        (workload, size) pairs)."""
        return sum(counts.stack_request_flops(self.layers[w])
                   for w, _ in info)
