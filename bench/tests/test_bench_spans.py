"""The readers of the program's phase and stall spans, on hand-made
windows: each batch's phase counted once over the requests the stage
handled, so the phases and the exec span's own time add up to
``exec_ms.rerank``; stalls clipped to the run; a program without such
spans reads None. And the trace reduction's whole output on the recorded
v5e trace, which these spans leave as it was."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from jzb import devtrace  # noqa: E402
from jzb.harness import Window  # noqa: E402
from jzb.manifest import Manifest  # noqa: E402
from repro.core.executors import RunReport, StageStats  # noqa: E402
from repro.core.sedp import Event  # noqa: E402

STAGE = "s.rerank"
PHASES = ("pack", "launch", "wait", "post")
# per phase: (pointwise span of batch A, of batch B, each candidate span)
DUR_MS = {"pack": (2.0, 4.0, 1.0), "launch": (0.5, 0.5, 0.25),
          "wait": (3.0, 3.0, 2.0), "post": (0.25, 0.5, 0.125)}


def _span(kind, t0, dur_ms, **attrs):
    return {"stage": STAGE, "kind": kind, "t0": t0,
            "t1": t0 + dur_ms / 1e3, "attrs": attrs}


def _window(phases=True, process_spans=()):
    """Three requests through the model stage: e0 and e1 in batch A, e2
    alone in batch B; each exec span 20 ms."""
    events = [Event(payload={}) for _ in range(3)]
    for ev in events:
        ev.meta["spans"] = [_span("queue", 0.0, 1.0),
                            _span("assemble", 0.001, 1.0)]
    batches = [events[:2], events[2:]]
    for b, batch in enumerate(batches):
        shared = [_span(k, 1.0, DUR_MS[k][b], parent=f"{STAGE}:exec",
                        batch=len(batch), call="pointwise") for k in PHASES]
        for ev in batch:
            own = [_span(k, 1.0, DUR_MS[k][2], parent=f"{STAGE}:exec",
                         batch=1, call="candidates") for k in PHASES]
            ev.meta["spans"] += (shared + own if phases else []) + [
                _span("exec", 1.0, 20.0, batch=len(batch))]
    report = RunReport(stage_stats={STAGE: StageStats(
        events=3, batches=2, busy_s=0.040)})
    report.process_spans = list(process_spans)
    return Window(cell=SimpleNamespace(scenario="s"), events=events,
                  due=np.array([10.0, 11.0, 12.0]),
                  release=np.array([10.0, 11.0, 12.0]), t_end=14.0,
                  seconds=3.0, report=report, answered=[0, 1, 2],
                  ok=np.ones(3, bool), latency_s=np.zeros(3))


def _reader(name):
    return Manifest.reader(name)


@pytest.mark.parametrize("kind", PHASES)
def test_phase_reader_counts_each_batch_span_once(kind):
    a, b, cand = DUR_MS[kind]
    want = (a + b + 3 * cand) / 3            # over the stage's 3 requests
    assert _reader(f"{kind}_ms.rerank")(_window()) == pytest.approx(want)


def test_phases_and_exec_self_time_add_up_to_exec_ms():
    w = _window()
    phases = sum(_reader(f"{k}_ms.rerank")(w) for k in PHASES)
    exec_ms = _reader("exec_ms.rerank")(w)
    # the exec spans' own time: 40 ms of busy over two batches, less the
    # phase time of each batch, over 3 requests
    self_ms = sum(20.0 - sum(DUR_MS[k][b] + n * DUR_MS[k][2]
                             for k in PHASES)
                  for b, n in ((0, 2), (1, 1))) / 3
    assert phases + self_ms == pytest.approx(exec_ms)


@pytest.mark.parametrize("name", [f"{k}_ms.rerank" for k in PHASES]
                         + ["stall_share"])
def test_program_without_the_spans_reads_none(name):
    w = _window(phases=False)
    w.report = SimpleNamespace(stage_stats=w.report.stage_stats)
    assert _reader(name)(w) is None


def test_stall_share_clips_stalls_to_the_run():
    stall = lambda t0, t1: {"stage": "process", "kind": "stall",  # noqa
                            "t0": t0, "t1": t1, "attrs": {}}
    assert _reader("stall_share")(_window()) == 0.0
    # the run is [10, 14]: 0.5 s inside, one stall half before it
    w = _window(process_spans=[stall(9.75, 10.25), stall(12.0, 12.25),
                               stall(20.0, 21.0)])
    assert _reader("stall_share")(w) == pytest.approx(100 * 0.5 / 4.0)


def test_recorded_v5e_trace_reduces_to_its_recorded_output():
    """Every output of ``devtrace.reduce`` on the recorded trace, as it
    read when the phase spans came in; the host spans it blames idle gaps
    on are the stages' own, not their phases."""
    doc = json.loads((BENCH / "testdata" / "din_trace_v5e.json")
                     .read_text())
    want = doc.pop("expect")
    got = devtrace.reduce(doc, tuple(want["window_ns"]),
                          kernels={"rerank_score": tuple(want["kernel"])},
                          modules=tuple(want["modules"]))
    recorded = json.loads((BENCH / "testdata" /
                           "din_trace_v5e.reduced.json").read_text())
    assert json.loads(json.dumps(got)) == recorded
    assert all("/" not in name for name, _ in got["idle_gaps"])
