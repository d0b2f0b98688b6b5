"""The generator: deterministic from the seed, the same work for every
seed, in the stated shares."""
import numpy as np
import pytest

from chip import traffic

CHAT = {"loop": "open", "arrivals": {"kind": "poisson", "rate_rps": 25.0},
        "prompt_len": {"values": [128, 256, 512, 1024],
                       "weights": [0.4, 0.3, 0.2, 0.1]},
        "output_len": {"kind": "loguniform", "low": 16, "high": 256}}
MIX = {"loop": "closed", "clients": 32,
       "mix": [["vikin-kan2", 1], ["vikin-mlp3", 1]]}
BIG_SEED = 2**31 + 12345


def sizes(cell, seed, n):
    s = traffic.Stream(cell, seed, block=n)
    return ([s.prompt_len(i) for i in range(n)],
            [s.output_len(i) for i in range(n)])


def test_same_seed_same_stream():
    a = traffic.arrival_times(CHAT["arrivals"], 10.0, BIG_SEED)
    b = traffic.arrival_times(CHAT["arrivals"], 10.0, BIG_SEED)
    assert np.array_equal(a, b)
    assert sizes(CHAT, BIG_SEED, 250) == sizes(CHAT, BIG_SEED, 250)


def test_seeds_differ_only_in_order():
    a = traffic.arrival_times(CHAT["arrivals"], 10.0, 1)
    b = traffic.arrival_times(CHAT["arrivals"], 10.0, BIG_SEED)
    assert not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(a, prepend=0.0)),
                       np.sort(np.diff(b, prepend=0.0)))
    assert a[-1] == pytest.approx(b[-1])
    pa, oa = sizes(CHAT, 1, 250)
    pb, ob = sizes(CHAT, BIG_SEED, 250)
    assert pa != pb and sorted(pa) == sorted(pb) and sorted(oa) == sorted(ob)


def test_poisson_count_and_span():
    t = traffic.arrival_times({"kind": "poisson", "rate_rps": 4000.0}, 10.0,
                              7)
    assert len(t) == 40000
    assert np.all(np.diff(t) > 0)
    assert 9.5 < t[-1] < 10.0


def test_shares_are_exact_per_block():
    s = traffic.Stream(MIX, 3)
    w = [s.workload(i) for i in range(3 * traffic.BLOCK)]
    for k in range(3):
        block = w[k * traffic.BLOCK:(k + 1) * traffic.BLOCK]
        assert block.count(0) == block.count(1) == traffic.BLOCK // 2
    prompts, outs = sizes(CHAT, 5, 1000)
    assert [prompts.count(v) for v in (128, 256, 512, 1024)] == \
        [400, 300, 200, 100]
    assert 16 <= min(outs) and max(outs) <= 256


def test_stratified_counts_largest_remainder():
    assert list(traffic.stratified_counts([1, 1, 1], 10)) == [4, 3, 3]
    with pytest.raises(ValueError):
        traffic.stratified_counts([0, 0], 3)


def test_bursty_is_seeded():
    spec = {"kind": "bursty", "rate_lo_rps": 100.0, "rate_hi_rps": 1000.0,
            "mean_calm_s": 0.5, "mean_burst_s": 0.1}
    a = traffic.arrival_times(spec, 5.0, 11)
    assert np.array_equal(a, traffic.arrival_times(spec, 5.0, 11))
    assert np.all(a < 5.0) and np.all(np.diff(a) > 0)
