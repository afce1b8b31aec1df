"""The trace reduction: busy union, idle share, kernel time by stable
name, program time, and idle gaps blamed on the host stage that ran in
them, on a hand-made trace and on one recorded on a TPU v5e."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from jzb import devtrace  # noqa: E402

RECORDED = BENCH / "testdata" / "din_trace_v5e.json"


def _doc():
    ops = [["fusion.3", 100, 50, {"hlo_module": "jit__lambda(12)"}],
           ["fusion.4", 120, 60, {"hlo_module": "jit__lambda(12)"}],
           ["%_rerank_score.1 = f32[128,1] custom-call(f32[32,18] %a)", 400,
            100, {"hlo_module": "jit__lambda(13)"}],
           ["%slice_reduce_fusion = f32[32] fusion(%_rerank_score.1)", 500,
            0, {"hlo_module": "jit__lambda(13)"}],
           ["fusion.9", 900, 200, {"hlo_module": "jit_fwd(2)"}]]
    mods = [["jit__lambda(12)", 100, 80, {}],
            ["jit__lambda(13)", 400, 100, {}],
            ["jit_fwd(2)", 900, 200, {}]]
    host = [["s.rerank", 0, 600, {"n": 16}],
            ["s.shed", 600, 290, {"n": 8}],
            ["s.rerank", 1150, 100, {"n": 4}]]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": mods},
                        "/device:CUSTOM:Megascale Trace": {}},
            "host": host}


def test_busy_union_and_idle_share():
    r = devtrace.reduce(_doc(), (0, 1200))
    # ops cover [100,180) [400,500) [900,1100): 380 of 1200 ns
    assert r["busy_s"] == pytest.approx(380e-9)
    assert r["window_s"] == pytest.approx(1200e-9)
    assert r["idle_share"] == pytest.approx(1 - 380 / 1200)


def test_kernel_and_program_time_by_name():
    r = devtrace.reduce(_doc(), (0, 1200),
                        kernels={"rerank_score": ("_rerank_score",)},
                        modules=("jit__lambda",))
    assert r["kernel_s"] == {"rerank_score": pytest.approx(100e-9)}
    assert r["kernel_calls"] == {"rerank_score": 1}
    assert r["module_s"] == pytest.approx(180e-9)
    names = dict(r["device_ops"])
    assert names["jit__lambda/fusion"] == pytest.approx(110e-9)
    assert names["jit_fwd/fusion"] == pytest.approx(200e-9)


def test_gaps_blamed_on_the_host_stage():
    r = devtrace.reduce(_doc(), (0, 1200))
    gaps = dict(r["idle_gaps"])
    # [0,100) and [180,400) under rerank; [500,900) shed 290 of 400 ns
    # against rerank's 100; [1100,1200) under the second rerank span
    assert gaps["s.rerank"] == pytest.approx((100 + 220 + 100) * 1e-9)
    assert gaps["s.shed"] == pytest.approx(400e-9)


def test_window_clips_ops():
    r = devtrace.reduce(_doc(), (450, 950))
    assert r["busy_s"] == pytest.approx((50 + 50) * 1e-9)


def test_recorded_v5e_trace():
    """A traced window of ``din.steady`` on one TPU v5e (the first 1,200
    device ops), reduced as the benchmark reduces it."""
    doc = json.loads(RECORDED.read_text())
    want = doc.pop("expect")
    r = devtrace.reduce(doc, tuple(want["window_ns"]),
                        kernels={"rerank_score": tuple(want["kernel"])},
                        modules=tuple(want["modules"]))
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["kernel_s"]["rerank_score"] == pytest.approx(
        want["kernel_s"], rel=1e-9)
    assert r["kernel_calls"]["rerank_score"] == want["kernel_calls"] == 22
    assert r["module_s"] == pytest.approx(want["module_s"], rel=1e-9)
    assert [g[0] for g in r["idle_gaps"]][:3] == want["top_gaps"]
    assert r["device_ops"][0][0] == want["top_op"] == "copy-done"
    # the device idles while the model stage's host code runs
    assert 0.9 < r["idle_share"] < 1
    assert want["top_gaps"][0] == "din-rerank.rerank"
