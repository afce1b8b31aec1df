"""A whole run of a cell, past the look for a chip, with the timed path
broken underneath: the result line says ``correct: false`` and names the
number that caught it; unbroken, the same run is correct."""
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from jzb.harness import run  # noqa: E402
from jzb.manifest import Manifest  # noqa: E402

SLOW = {"rate_rps": 40, "deadline_ms": 60_000}   # the CPU keeps up


def _alter_scores(cell):
    serve = cell.rt.serve
    cell.rt.serve = lambda p, b: serve(p, b) * 0.99


def test_altered_answer_makes_the_run_incorrect():
    out = run("din.steady", 5_000_000_003, 1.0, False, time.monotonic(),
              fault=_alter_scores, mix=SLOW, reduced=True, allow_cpu=True,
              cache=False)
    assert out["correct"] is False
    assert out["checks"]["point_gap"]["value"] > \
        out["checks"]["point_gap"]["limit"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "p50_ms", "goodput_rps"}
    assert out["failed"] == 0 and out["attempted"] == 40


def test_unbroken_run_is_correct():
    out = run(sorted(Manifest().cells)[0], 5_000_000_004, 1.0, False,
              time.monotonic(),
              mix=SLOW, reduced=True, allow_cpu=True, cache=False)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["goodput_rps"]["value"] > 0
