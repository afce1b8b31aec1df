"""chip_smoke.py on the CPU: its phases (b)-(d) pass at reduced widths and
catch a wrong answer, and its entry point refuses to run without a TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def served(smoke):
    svc = smoke.build_service(reduced=True)
    try:
        yield svc, smoke.serve_requests(svc)
    finally:
        shutil.rmtree(svc.cube.tmpdir, ignore_errors=True)


def test_phases_pass_at_reduced_widths(smoke, served):
    svc, rep = served
    assert rep.completed == smoke.N_REQUESTS
    got = smoke.check_reference(svc, rep)
    assert got["bound"] == smoke.SCORE_BOUND["cpu"]
    assert got["pointwise"] <= got["bound"] and got["topk"] <= got["bound"]
    smoke.report_compiles(svc, smoke.CompileStats())


@pytest.mark.parametrize("where", ["score", "topk"])
def test_reference_check_catches_a_wrong_answer(smoke, served, where):
    svc, rep = served
    ev = next(ev for ev in rep.results if ev.payload.get("topk"))
    saved = ev.payload[where]
    bump = 10 * smoke.SCORE_BOUND["cpu"]
    ev.payload[where] = (saved + bump if where == "score" else
                         [(item, s + bump) for item, s in saved])
    try:
        with pytest.raises(smoke.PhaseFailed):
            smoke.check_reference(svc, rep)
    finally:
        ev.payload[where] = saved


def test_entry_point_exits_nonzero_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
