"""Launch layer: sharding rules, input specs, sharded==dense equivalence."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS, get_config, runnable_cells
from repro.launch import sharding as SH
from repro.launch import specs as SP
from repro.launch.mesh import make_host_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_runnable_cells_count():
    """40 assigned cells minus the 8 documented long_500k skips."""
    cells = runnable_cells()
    assert len(cells) == 32
    long_archs = {a for a, s in cells if s == "long_500k"}
    assert long_archs == {"xlstm-125m", "recurrentgemma-9b"}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_input_specs_all_cells(arch):
    cfg = get_config(arch)
    for sname, shape in SHAPES.items():
        if sname == "long_500k" and not cfg.subquadratic:
            continue
        specs = SP.input_specs(cfg, shape)
        if shape.kind in ("train", "prefill"):
            t = specs["tokens"]
            assert t.shape[0] == shape.global_batch
            assert t.dtype == jnp.int32
        else:
            assert specs["token"].shape == (shape.global_batch, 1)
            assert "caches" in specs
        # every leaf must be abstract (no allocation)
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_bad_ffn_kinds_raise_named_error_at_construction():
    """Invalid per-layer kinds fail at config build with ArchConfigError,
    not as a shape-mismatch crash deep inside block_init (regression:
    registry.get_serving_config used to hand such configs through)."""
    import dataclasses

    from repro.configs.base import ArchConfigError
    from repro.configs.registry import KANFFN_ARCHS, get_serving_config

    good = KANFFN_ARCHS["kanffn-ci"]
    with pytest.raises(ArchConfigError, match="unknown ffn_kinds"):
        dataclasses.replace(good, ffn_kinds=("mlp", "KAN", "mlp"))
    with pytest.raises(ArchConfigError, match="entries"):
        dataclasses.replace(good, ffn_kinds=("mlp", "kan"))
    with pytest.raises(ArchConfigError, match="scan_layers"):
        dataclasses.replace(good, scan_layers=True)
    with pytest.raises(ArchConfigError, match="moe"):
        dataclasses.replace(good, ffn_kinds=("mlp", "moe", "mlp"))
    with pytest.raises(ArchConfigError, match="ffn_masks"):
        dataclasses.replace(good, ffn_masks=(None, None))
    # the registry resolves kan-ffn archs as transformers, and they stay
    # OUT of the dry-run grid (runnable_cells pin above)
    fam, cfg = get_serving_config("kanffn-ci")
    assert fam == "transformer" and cfg.ffn_kinds is not None
    assert not set(KANFFN_ARCHS) & set(ARCHS)
    with pytest.raises(KeyError, match="kan-ffn archs"):
        get_serving_config("no-such-arch")


def test_param_sharding_rules_cover_paths():
    """Every parameter gets a sharding; attn/ffn kernels get model axes."""
    cfg = get_config("qwen2-0.5b")
    from repro.models.transformer import param_shapes
    shapes = param_shapes(cfg)
    mesh = make_host_mesh()
    sh = SH.param_shardings(shapes, mesh)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    assert len(flat) == len(jax.tree.leaves(shapes))
    by_path = {jax.tree_util.keystr(p): s for p, s in flat}
    wq = [s for p, s in by_path.items() if "wq" in p and "kernel" in p]
    assert all("model" in str(s.spec) for s in wq)


def test_fsdp_adds_data_axis():
    cfg = get_config("granite-20b")
    from repro.models.transformer import param_shapes
    shapes = param_shapes(cfg)
    mesh = make_host_mesh()
    plain = SH.param_shardings(shapes, mesh, fsdp=False)
    fsdp = SH.param_shardings(shapes, mesh, fsdp=True)
    n_data_plain = sum("data" in str(s.spec) for s in jax.tree.leaves(plain))
    n_data_fsdp = sum("data" in str(s.spec) for s in jax.tree.leaves(fsdp))
    assert n_data_fsdp > n_data_plain


def test_zero1_no_duplicate_axes():
    cfg = get_config("llama4-scout-17b-a16e")
    from repro.launch.steps import train_state_shardings
    sh = train_state_shardings(cfg, make_host_mesh())
    for s in jax.tree.leaves(sh.__dict__ if hasattr(sh, "__dict__") else sh):
        spec = getattr(s, "spec", None)
        if spec is None:
            continue
        axes = [a for part in spec for a in
                (part if isinstance(part, tuple) else (part,))
                if a is not None]
        assert len(axes) == len(set(axes)), f"duplicate axis in {spec}"


SHARDED_EQ_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.registry import get_config
    from repro.launch.steps import make_train_step, init_train_state, \\
        StepOptions, train_state_shardings
    from repro.launch.sharding import batch_shardings
    import dataclasses

    arch = sys.argv[1]
    cfg = get_config(arch).reduce(n_layers=2, d_model=32, d_ff=64,
                                  vocab_size=64, n_heads=4, n_kv_heads=2)
    if cfg.n_experts:
        # capacity is defined per data shard, so drop behaviour is mesh-
        # dependent by design; compare at no-drop capacity for exactness
        cfg = dataclasses.replace(cfg, n_experts=4, top_k=2,
                                  capacity_factor=8.0)
    batch = {"tokens": np.random.default_rng(0).integers(
        0, 64, size=(8, 17)).astype(np.int32)}

    def run(mesh):
        with jax.set_mesh(mesh):
            state = init_train_state(jax.random.key(0), cfg)
            step = make_train_step(cfg, mesh, StepOptions(lr=1e-3,
                                                          total_steps=10))
            b = jax.device_put(batch, batch_shardings(batch, mesh))
            for _ in range(2):
                state, metrics = jax.jit(step)(state, b)
            return float(metrics["loss"]), state

    types = (jax.sharding.AxisType.Auto,) * 2
    mesh1 = jax.make_mesh((1, 1), ("data", "model"), axis_types=types)
    mesh8 = jax.make_mesh((2, 4), ("data", "model"), axis_types=types)
    l1, s1 = run(mesh1)
    l8, s8 = run(mesh8)
    diff = max(float(np.max(np.abs(
        np.asarray(jax.device_get(a), np.float32)
        - np.asarray(jax.device_get(b), np.float32))))
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s8["params"])))
    print(json.dumps({"loss1": l1, "loss8": l8, "max_param_diff": diff}))
""")


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "qwen3-moe-235b-a22b",
                                  "recurrentgemma-9b"])
def test_sharded_equals_dense_subprocess(arch):
    """Train 2 steps on a 1-device and a 2x4 mesh: identical results.

    This is the fundamental SPMD correctness contract; runs in a
    subprocess because forcing 8 host devices must precede jax init.
    """
    r = subprocess.run(
        [sys.executable, "-c", SHARDED_EQ_SCRIPT, arch],
        capture_output=True, text=True, cwd=REPO, timeout=600,
        env={**os.environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert abs(out["loss1"] - out["loss8"]) < 1e-3, out
    assert out["max_param_diff"] < 1e-3, out


@pytest.fixture()
def cache_config():
    """Restore the compile-cache settings enable_compile_cache touches."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_fixed_path_in_checkout(
        monkeypatch, cache_config):
    from repro.utils import REPO_ROOT, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(str(REPO_ROOT), ".jax_cache")
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.realpath(str(REPO_ROOT)) == os.path.realpath(REPO)


def test_compile_cache_env_dir_is_left_alone(monkeypatch, cache_config,
                                             tmp_path):
    from repro.utils import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory of its own
    assert jax.config.jax_compilation_cache_dir is None


def test_autotune_cache_defaults_into_checkout(monkeypatch):
    from repro.kernels import autotune

    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert os.path.realpath(autotune.default_cache_path()) == \
        os.path.realpath(os.path.join(REPO, ".autotune", "autotune.json"))
