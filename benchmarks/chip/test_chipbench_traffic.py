"""Traffic schedules: the same seed gives the same schedule, every seed
the same work, and the drawn lengths follow each mix file."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import registry, traffic as T  # noqa: E402

# a mix of two phases, as a file under traffic/ would hold it
TWO_PHASE = {
    "name": "two_phase", "arrivals": "poisson",
    "phases": [
        {"name": "night", "window_seconds": 10, "rate_knee_share": 0.5,
         "prompt_len": {"256": 0.7, "512": 0.3},
         "output_len": {"median": 48, "sigma": 0.6, "min": 8, "max": 128}},
        {"name": "day", "rate_knee_share": 0.6,
         "prompt_len": {"1024": 0.5, "2048": 0.5},
         "output_len": {"median": 256, "sigma": 0.6, "min": 64,
                        "max": 512}}]}
MIXES = ("chat", "two_phase")


def load(name):
    return TWO_PHASE if name == "two_phase" else registry.traffic(name)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_schedule(mix):
    m = load(mix)
    seed = 2**31 + 977                    # past 32 signed bits
    a = T.schedule(m, 1.5, 51, 96, seed)
    b = T.schedule(m, 1.5, 51, 96, seed)
    for x, y in zip(a, b):
        for f in ("due", "prompt_len", "gen", "phase"):
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work(mix):
    m = load(mix)
    a = T.schedule(m, 1.5, 51, 96, 1)[1]
    b = T.schedule(m, 1.5, 51, 96, 2)[1]
    assert not np.array_equal(a.gen, b.gen)          # another order
    for f in ("prompt_len", "gen", "phase"):
        np.testing.assert_array_equal(np.sort(getattr(a, f)),
                                      np.sort(getattr(b, f)))
    np.testing.assert_allclose(np.sort(np.diff(a.due)),
                               np.sort(np.diff(b.due)), rtol=0.3,
                               atol=0.5)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_follow_the_mix_file(mix):
    m = load(mix)
    knee, seconds = 2.0, 51.0
    warm, win = T.schedule(m, knee, seconds, 96, 5)
    spans = T.phase_spans(m, seconds)
    assert np.all(np.diff(win.due) >= 0)
    for i, (ph, (lo, hi)) in enumerate(zip(m["phases"], spans)):
        sel = win.phase == i
        n = int(sel.sum())
        assert n == round(ph["rate_knee_share"] * knee * (hi - lo))
        assert np.all((win.due[sel] >= lo) & (win.due[sel] < hi))
        for L, share in ph["prompt_len"].items():
            assert abs(np.sum(win.prompt_len[sel] == int(L)) - share * n) < 1
        out = win.gen[sel] + 1
        spec = ph["output_len"]
        assert out.min() >= spec["min"] and out.max() <= spec["max"]
        assert abs(np.median(out) - spec["median"]) <= 0.1 * spec["median"]
    first = m["phases"][0]
    assert len(warm) == 96 and set(warm.prompt_len) <= {
        int(k) for k in first["prompt_len"]}


def test_output_quantiles_are_the_lognormal_mid_quantiles():
    q = T.output_quantiles({"median": 100, "sigma": 1.0, "min": 1,
                            "max": 10**6}, 3)
    # mid-quantiles 1/6, 1/2, 5/6 of N(0, 1) are -0.9674, 0, 0.9674
    np.testing.assert_array_equal(q, np.rint(100 * np.exp(
        [-0.96742157, 0.0, 0.96742157])))


def test_prompt_shares_must_sum_to_one():
    with pytest.raises(ValueError):
        T.draw_phase({"prompt_len": {"8": 0.5, "16": 0.2},
                      "output_len": {"median": 4, "sigma": 0.1, "min": 1,
                                     "max": 8}}, 10, 5.0,
                     np.random.default_rng(0))
