"""Compile the served Pallas kernels for a described TPU v5e, at real widths.

No chip is needed: ``jax.experimental.topologies`` describes a v5e:2x2
host and the TPU compiler (Mosaic included) runs here on the CPU, so a
tile Mosaic refuses -- a block that is neither 128-lane aligned nor the
whole axis, a value shape it cannot cast, more scoped VMEM than the core
has -- fails here as it would on the chip.  Nothing runs, so numerics are
``chip_smoke.py``'s job.

Every kernel is compiled through the same ``resolve_blocks`` its
``impl="auto"`` dispatch uses, at the shapes the serving paths send it:
the paper stacks' layers (vikin-kan2, vikin-mlp3, vikin-mixed) at the
engine buckets 2 and 16, and the qwen2-0.5b-kanffn FFN (KAN 896->1080 up;
1080->896 down, dense and with half the hidden lanes kept) at 16 and 128
rows.  The topology is described inside a fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.

The served programs themselves are compiled too, under their stable names
(``transformer_decode``, ``transformer_prefill`` at the full
qwen2-0.5b-kanffn width, ``vikin_forward`` of vikin-mixed): the device
trace's readers find the kernels by the instruction names checked here.
So is the prefill's cache splice, ``transformer_splice``, at the served
slot caches, with the slot a runtime argument.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import KANFFN_ARCHS
from repro.configs.vikin_models import VIKIN_ARCHS
from repro.core.splines import SplineSpec
from repro.kernels import autotune
from repro.kernels.kan_fused import ops as kan_ops
from repro.kernels.kan_fused.kan_fused import (
    VMEM_LIMIT,
    kan_fused_pallas_v2,
    kan_fused_pallas_v2_q8,
    vmem_bytes,
)
from repro.kernels.pattern_matmul import ops as pm_ops
from repro.kernels.pattern_matmul.pattern_matmul import (
    matmul_compact_pallas,
    matmul_q8_pallas,
)
from repro.models import transformer as T
from repro.models.ffn import stack_layer_cfgs, vikin_stack_init
from repro.runtime.backends import TransformerBackend, VikinBackend


def _paper_layers():
    """(name, n_in, n_out, nbk) KAN layers and (name, K, N) matmuls of the
    three served paper stacks, stage-2 masks applied."""
    kan, mm = {}, {}
    for arch in ("vikin-kan2", "vikin-mlp3", "vikin-mixed"):
        for i, (kind, c) in enumerate(stack_layer_cfgs(VIKIN_ARCHS[arch])):
            if kind == "kan":
                kan[(c.n_in, c.n_out, c.n_bases_kept)] = f"{arch}.{i}"
            else:
                k = c["n_in"] if c["mask"] is None else c["mask"].n_keep
                mm[(k, c["n_out"])] = f"{arch}.{i}"
    return ([(n, *s) for s, n in kan.items()],
            [(n, *s) for s, n in mm.items()])


PAPER_KAN, PAPER_MM = _paper_layers()
SPEC = SplineSpec(4, 3)
QWEN_KAN = [("qwen2-0.5b-kanffn.up", 896, 1080, SPEC.n_bases)]
QWEN_MM = [("qwen2-0.5b-kanffn.down", 1080, 896),
           ("qwen2-0.5b-kanffn.down-half", 540, 896)]

KAN_CASES = ([(n, b, i, o, k) for n, i, o, k in PAPER_KAN for b in (2, 16)]
             + [(n, b, i, o, k) for n, i, o, k in QWEN_KAN for b in (16, 128)])
MM_CASES = ([(n, b, k, o) for n, k, o in PAPER_MM for b in (2, 16)]
            + [(n, b, k, o) for n, k, o in QWEN_MM for b in (16, 128)])
DTYPES = ["float32", "bfloat16", "int8"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel"
    return compiled


def _kan_compile(one_chip, B, n_in, n_out, nbk, dtype, blocks):
    kb = tuple(range(nbk))
    spec = SplineSpec(4, 3)
    x = jax.ShapeDtypeStruct((B, n_in), dtype, sharding=one_chip)
    wt = jax.ShapeDtypeStruct((n_in * (nbk + 1), n_out), dtype,
                              sharding=one_chip)
    if jnp.dtype(dtype) == jnp.int8:
        ss = jax.ShapeDtypeStruct((1, nbk + 1), jnp.float32,
                                  sharding=one_chip)
        return _compile(lambda x, w, s: kan_fused_pallas_v2_q8(
            x, w, s, spec, kb, x_scale=0.05, **blocks), x, wt, ss)
    return _compile(lambda x, w: kan_fused_pallas_v2(
        x, w, spec, kb, **blocks), x, wt)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layer,B,n_in,n_out,nbk", KAN_CASES)
def test_kan_fused_v2_compiles(one_chip, layer, B, n_in, n_out, nbk, dtype):
    blocks = kan_ops.resolve_blocks(B, n_in, n_out, nbk, dtype)
    _kan_compile(one_chip, B, n_in, n_out, nbk, dtype, blocks)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("layer,M,K,N", MM_CASES)
def test_pattern_matmul_compiles(one_chip, layer, M, K, N, dtype):
    blocks = pm_ops.resolve_blocks(M, K, N, dtype)
    x = jax.ShapeDtypeStruct((M, K), dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((K, N), dtype, sharding=one_chip)
    if dtype == "int8":
        _compile(lambda x, w: matmul_q8_pallas(x, w, **blocks), x, w)
    else:
        b = jax.ShapeDtypeStruct((N,), dtype, sharding=one_chip)
        _compile(lambda x, w, b: matmul_compact_pallas(
            x, w, b, act="relu", **blocks), x, w, b)


@pytest.mark.parametrize("dtype", DTYPES)
def test_largest_autotune_candidate_compiles(one_chip, dtype):
    """The autotune grid offers only tiles that compile: its largest
    candidate by the lane-padded VMEM model, at the qwen up-projection."""
    _, n_in, n_out, nbk = QWEN_KAN[0]
    cands = autotune.candidates_kan_fused(128, n_in, n_out, nbk, dtype)
    big = max(cands, key=lambda c: vmem_bytes(c["bm"], c["bi"], c["bn"],
                                              nbk, dtype))
    assert vmem_bytes(big["bm"], big["bi"], big["bn"], nbk,
                      dtype) <= VMEM_LIMIT
    _kan_compile(one_chip, 128, n_in, n_out, nbk, dtype, big)


def _on(one_chip, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), tree)


def _served_transformer(one_chip, max_len):
    """qwen2-0.5b-kanffn as the backend serves it: bf16, the compiled
    kernels (``impl="pallas"``), 8 slots of ``max_len``."""
    arch = dataclasses.replace(KANFFN_ARCHS["qwen2-0.5b-kanffn"],
                               dtype="bfloat16")
    params = jax.eval_shape(functools.partial(T.init_params, cfg=arch),
                            jax.random.key(0))
    b = TransformerBackend(arch, _on(one_chip, params), impl="pallas",
                           precision="bf16")
    b.n_slots, b.max_len = 8, max_len
    return b


def _served_program(one_chip, name):
    """(jitted program, argument shapes) as the backend serves ``name``:
    the compiled kernels (``impl="pallas"``), 8 slots."""
    if name == "vikin_forward":
        model = VIKIN_ARCHS["vikin-mixed"]
        params = jax.eval_shape(functools.partial(
            vikin_stack_init, model=model), jax.random.key(0))
        b = VikinBackend(model, _on(one_chip, params), impl="pallas")
        x = jax.ShapeDtypeStruct((8, b.n_in), jnp.float32, sharding=one_chip)
        return b._fwd, (b.params, x)
    b = _served_transformer(one_chip, max_len=64)
    if name == "transformer_prefill":
        toks = jax.ShapeDtypeStruct((1, 16), jnp.int32, sharding=one_chip)
        return b._prefill_fn(16), (b.params, toks)
    caches = jax.eval_shape(lambda: T.init_caches(b.cfg, 8, 64))
    toks = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    return b._decode, (b.params, toks, _on(one_chip, caches))


@pytest.mark.parametrize("name", ["transformer_decode",
                                  "transformer_prefill", "vikin_forward"])
def test_served_program_holds_the_named_kernels(one_chip, name):
    """The served programs keep their names and both Mosaic kernels under
    the instruction names ``kan_roofline`` / ``pmm_roofline`` search for:
    a rename must fail here, not leave those metrics silently empty."""
    fn, args = _served_program(one_chip, name)
    text = fn.lower(*args).compile().as_text()
    assert text.startswith(f"HloModule jit_{name},")
    instrs = [line.split(" = ")[0] for line in text.splitlines()
              if " = " in line]
    for kernel in ("kan_fused_pallas_v2", "matmul_compact_pallas"):
        assert any(kernel in i for i in instrs), kernel


def test_splice_is_one_program_with_the_slot_a_runtime_argument(one_chip):
    """The prefill's cache splice compiles as ``jit_transformer_splice`` at
    the served shapes (8 slots of 2064, the longprompt cell's), and takes the
    slot as an s32[] parameter: one executable serves every slot."""
    b = _served_transformer(one_chip, max_len=2064)
    caches = jax.eval_shape(lambda: T.init_caches(b.cfg, 8, 2064))
    cache = jax.eval_shape(lambda: T.init_caches(b.cfg, 1, 2064))
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = b._splice.lower(_on(one_chip, caches), _on(one_chip, cache),
                           slot).compile().as_text()
    assert text.startswith("HloModule jit_transformer_splice,")
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    params = [line for line in entry.splitlines() if " parameter(" in line]
    assert len(params) == 2 * len(jax.tree.leaves(caches)) + 1
    slot_params = [line for line in params
                   if re.search(r"= s32\[\]\S* parameter\(", line)]
    assert len(slot_params) == 1 and 'op_name="slot"' in slot_params[0]
