"""The benchmark's traffic generator: one seed gives identical requests,
and the distributions follow the traffic file's parameters."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from jzb import traffic as tg  # noqa: E402

CFG = json.loads((BENCH / "configs" / "din-rerank.json").read_text())
MIX = json.loads((BENCH / "traffic" / "din.steady.json").read_text())
SEED = 3_000_000_019          # past 2**31, as the benchmark's seeds are


@pytest.fixture(scope="module")
def two():
    return (tg.make_traffic(CFG, MIX, SEED, 4.0),
            tg.make_traffic(CFG, MIX, SEED, 4.0))


def test_same_seed_same_requests(two):
    a, b = two
    for f in ("due_s", "user", "item", "cand_off", "cand_ids",
              "cand_scores"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    for u in a.user[:50]:
        np.testing.assert_array_equal(tg.user_history(CFG, MIX, int(u)),
                                      tg.user_history(CFG, MIX, int(u)))
        assert (tg.user_fields(CFG, int(u))["user_profile"]
                == tg.user_fields(CFG, int(u))["user_profile"]).all()


def test_other_seed_same_sizes_other_order(two):
    a, _ = two
    c = tg.make_traffic(CFG, MIX, SEED + 1, 4.0)
    counts = np.diff(a.cand_off)
    other = np.diff(c.cand_off)
    np.testing.assert_array_equal(np.sort(counts), np.sort(other))
    assert not np.array_equal(counts, other)
    np.testing.assert_allclose(np.sort(np.diff(a.due_s)),
                               np.sort(np.diff(c.due_s)), rtol=0.3,
                               atol=1e-3)
    assert not np.array_equal(a.user, c.user)


def test_arrivals_fill_the_window_at_the_rate(two):
    a, _ = two
    n = len(a)
    assert n == round(MIX["rate_rps"] * 4.0)
    assert a.due_s[0] == 0.0 and a.due_s[-1] < 4.0
    assert np.all(np.diff(a.due_s) > 0)
    gaps = np.diff(a.due_s) * MIX["rate_rps"]
    # exponential gaps: mean 1, coefficient of variation 1
    assert abs(gaps.mean() - 1.0) < 0.05
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15


def test_candidates_lognormal_distinct_zipf(two):
    a, _ = two
    cd = MIX["candidates"]
    counts = np.diff(a.cand_off)
    assert counts.min() >= cd["min"] and counts.max() <= cd["max"]
    assert abs(np.median(counts) - cd["median"]) <= 1
    # clipped lognormal: the share above the median's e^sigma multiple
    above = np.mean(counts > cd["median"] * np.exp(cd["sigma"]))
    assert abs(above - 0.1587) < 0.02
    for i in range(0, len(a), 97):
        ids, scores = a.candidates(i)
        assert len(set(ids.tolist())) == len(ids)
        assert ((scores >= 0) & (scores < 1)).all()
    # Zipf over the item vocabulary: id 0 is the most drawn item, and a
    # wide set of ids appears (not a fixed 0..63 working set)
    assert np.bincount(a.cand_ids[a.cand_ids < 100]).argmax() == 0
    assert len(np.unique(a.cand_ids)) > 10 * cd["max"]


def test_ids_zipf_over_the_vocabularies(two):
    a, _ = two
    share0 = np.mean(a.user == 0)
    # P(rank 1) = 1 / zeta(1.05) ~ 0.049 (the tail past 64 Mi folds back)
    assert 0.03 < share0 < 0.07
    assert a.user.max() < CFG["user_fields"][0]["vocab"]
    assert a.item.max() < CFG["item_fields"][0]["vocab"]


def test_history_lengths_lognormal_by_user():
    hd = MIX["history"]
    lengths = np.array([(tg.user_history(CFG, MIX, u) >= 0).sum()
                        for u in range(2000)])
    assert lengths.min() >= hd["min"] and lengths.max() <= hd["max"]
    assert abs(np.median(lengths) - hd["median"]) <= 3
    h = tg.user_history(CFG, MIX, 7)
    n = (h >= 0).sum()
    assert (h[:n] >= 0).all() and (h[n:] == -1).all()


def test_burst_arrivals_copy_is_seeded():
    mix = dict(MIX, arrivals={"kind": "burst", "burst_rate_per_s": 0.2,
                              "burst_mult": 3.0, "burst_dur_s": 0.5})
    t1 = tg.arrival_times(mix, 20.0, np.random.default_rng(5))
    t2 = tg.arrival_times(mix, 20.0, np.random.default_rng(5))
    np.testing.assert_array_equal(t1, t2)
    assert t1.max() < 20.0 and np.all(np.diff(t1) >= 0)
    assert len(t1) > MIX["rate_rps"] * 20.0 * 0.9
