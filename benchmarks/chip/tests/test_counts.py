"""counts.py against FLOPs and bytes worked out by hand at the paper's and
the model's published widths."""
import json
import os

import pytest

from chip import counts
from chip.systems.kanffn_transformer import normalized
from chip.systems.vikin_stacks import model_layers

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        return json.load(f)


def test_kan_layer_table2_widths():
    # KAN-2: 72 -> 96, 5 of 7 bases kept: silu + 5 bases = 6 MACs/edge
    flops, nbytes = counts.kan_layer(8, 72, 96, 5, 4)
    assert flops == 2 * 8 * 72 * 6 * 96 == 663552
    assert nbytes == 4 * (8 * 72 + 72 * 6 * 96 + 8 * 96) == 171264


def test_stack_request_flops_table2():
    models = config("vikin-table2")["models"]
    kan = model_layers(models["vikin-kan2"])
    mlp = model_layers(models["vikin-mlp3"])
    assert kan[0]["basis_keep"] == [0, 2, 4, 5, 6]
    assert len(mlp[1]["in_keep"]) == 228 and mlp[0]["in_keep"] == list(
        range(72))
    assert counts.stack_request_flops(kan) == 2 * 72 * 6 * 96 == 82944
    assert counts.stack_request_flops(mlp) == 2 * 72 * 304 + 2 * 228 * 96


def test_kanffn_kernel_calls_at_1024_rows():
    cfg = normalized(config("qwen2-0.5b-kanffn"))
    calls = counts.kanffn_kernel_calls(cfg, 1024)
    assert calls["kan"] == (2.0 * 1024 * 896 * 8 * 1080,
                            2.0 * (1024 * 896 + 896 * 8 * 1080
                                   + 1024 * 1080))
    assert calls["pmm"] == (2.0 * 1024 * 1080 * 896,
                            2.0 * (1024 * 1080 + 1080 * 896 + 1024 * 896
                                   + 896))
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # compute-bound at 1024 rows, memory-bound at a decode step's 8
    assert counts.least_time_s(*calls["kan"], peaks) == pytest.approx(
        calls["kan"][0] / 197e12)
    dec = counts.kanffn_kernel_calls(cfg, 8)["kan"]
    assert counts.least_time_s(*dec, peaks) == pytest.approx(dec[1] / 819e9)


def test_qwen2_kanffn_parameters_and_step_flops():
    cfg = normalized(config("qwen2-0.5b-kanffn"))
    attn = 896 * 896 * 2 + 2 * 896 * 128 + 896 + 2 * 128
    swiglu, kan = 3 * 896 * 4864, 896 * 1080 * 8 + 1080 * 896 + 896
    n = 896 + 12 * (attn + swiglu + 2 * 896) + 12 * (attn + kan + 2 * 896)
    assert counts.nonembedding_params(cfg) == n == 305525120
    head = 2 * 896 * 151936
    assert counts.prefill_flops(cfg, 4) == 2 * n * 4 + 24 * 4 * 10 * 896 \
        + head
    assert counts.decode_flops(cfg, [0, 9]) == 2 * (2 * n + head) \
        + 24 * 4 * (1 + 10) * 896
