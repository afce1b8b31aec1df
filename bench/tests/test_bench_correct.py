"""``correct`` on the CPU, at the program's CPU-sized widths: the served
path passes its limits, the fp8 control fails them, and each way the
timed path can be broken underneath makes ``correct`` false."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from jzb import check  # noqa: E402
from jzb.harness import N_SAMPLE, Cell  # noqa: E402
from jzb.manifest import Manifest  # noqa: E402

SEED = 4_000_000_007
SLOW = {"rate_rps": 40, "deadline_ms": 60_000}   # the CPU keeps up


@pytest.fixture(scope="module", params=sorted(Manifest().cells))
def cell(request):
    c = Cell(request.param, reduced=True, allow_cpu=True, cache=False)
    c.traffic.update(SLOW)
    c.build()
    c.install_weights(SEED)
    tr, _ = c.make_events(SEED, 1.0)
    c.warm(tr)
    return c


def _window(cell):
    """A window of fresh requests, with no score cached by an earlier
    test (a broken path must be caught where it serves)."""
    cell.svc.query_cache.bump_model_version()
    _window.seed += 1
    tr, events = cell.make_events(_window.seed, 1.0)
    return cell.window(tr, events, 1.0, trace=False)


_window.seed = SEED


def _judge(cell, w, control=False):
    readings = check.compare(w, cell.params, SEED, N_SAMPLE, control=control)
    if control:
        readings.update(shed_violations=0, shed_share=0.0, unanswered=0)
    return check.judge(readings, cell.limits)


def test_served_path_is_correct(cell):
    w = _window(cell)
    assert len(w.reranked()) > 10
    checks, ok = _judge(cell, w)
    assert ok, checks
    assert checks["point_gap"]["value"] < checks["point_gap"]["limit"] / 10


def test_control_fails(cell):
    checks, ok = _judge(cell, _window(cell), control=True)
    assert not ok, checks


def _patched(monkeypatch, obj, name, wrap):
    monkeypatch.setattr(obj, name, wrap(getattr(obj, name)))


def test_pointwise_answer_altered(cell, monkeypatch):
    _patched(monkeypatch, cell.rt, "serve",
             lambda f: lambda p, b: f(p, b) + 1e-2)
    checks, ok = _judge(cell, _window(cell))
    assert not ok and checks["point_gap"]["value"] > 1e-3


def test_topk_scores_altered(cell, monkeypatch):
    _patched(monkeypatch, cell.rt, "rerank",
             lambda f: lambda p, u, c: (lambda v, i: (v + 0.1, i))(
                 *f(p, u, c)))
    checks, ok = _judge(cell, _window(cell))
    assert not ok and checks["cand_gap"]["value"] > 1e-3


def test_ranking_reversed(cell, monkeypatch):
    _patched(monkeypatch, cell.rt, "rerank",
             lambda f: lambda p, u, c: (lambda v, i: (v[::-1], i[::-1]))(
                 *f(p, u, c)))
    checks, ok = _judge(cell, _window(cell))
    assert not ok and checks["rank_violations"]["value"] > 0


def test_shed_keeps_the_wrong_candidates(cell, monkeypatch):
    def worst_first(op):
        def shed(batch, ctx):
            for ev in batch:
                c = ev.payload["candidates"]
                ev.payload["candidates"] = sorted(c, key=lambda x: x[1])[:12]
            return batch
        return shed
    _patched(monkeypatch, cell.rt.shedder, "op", worst_first)
    checks, ok = _judge(cell, _window(cell))
    assert not ok and checks["shed_violations"]["value"] > 0


def test_shed_keeps_only_its_floor(cell, monkeypatch):
    def floor_only(op):
        def shed(batch, ctx):
            out = op(batch, ctx)
            for ev in out:
                c = ev.payload["candidates"]
                ev.payload["candidates"] = c[:cell.rt.shedder.min_keep]
            return out
        return shed
    _patched(monkeypatch, cell.rt.shedder, "op", floor_only)
    checks, ok = _judge(cell, _window(cell))
    assert not ok and checks["shed_share"]["value"] > \
        checks["shed_share"]["limit"]
    assert checks["shed_violations"]["value"] == 0


def test_errored_requests_fail(cell, monkeypatch):
    calls = {"n": 0}

    def flaky(f):
        def serve(p, b):
            calls["n"] += 1
            if calls["n"] % 3 == 0:
                raise RuntimeError("injected")
            return f(p, b)
        return serve
    _patched(monkeypatch, cell.rt, "serve", flaky)
    checks, ok = _judge(cell, _window(cell))
    assert not ok and checks["unanswered"]["value"] > 0


def test_sample_holds_the_biggest_request(cell):
    w = _window(cell)
    big = max(w.reranked(), key=lambda i: w.work(i)[0] * w.work(i)[1])
    assert big in check.sample(w, SEED, 8)
    assert np.all(np.diff(w.due) > 0)
