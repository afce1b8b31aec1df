"""Program spans below the exec span and the whole-process stall monitor
(DESIGN.md §10.1): the model stage's pack, launch, wait and post phases
nest in its exec span on a traced ``AsyncExecutor`` run and nowhere else,
served answers do not depend on tracing, ``StallMonitor`` records a late
tick with what the process was charged and what each thread had open,
process spans round-trip through the Chrome export, and
``critical_path`` counts only top-level spans as covered."""
import dataclasses
import random
import threading
import time

import numpy as np
import pytest

import repro.core.executors as executors
from repro.core.executors import AsyncExecutor
from repro.core.sedp import SEDP, Event
from repro.core.service import MultiScenarioService, MultiServiceConfig
from repro.obs.trace import (StallMonitor, TraceBuffer, Tracer,
                             critical_path, phase, span_topology)
from repro.serve.scenario import get_scenario
from repro.serve.stages import stage_of

PHASES = ("pack", "launch", "wait", "post")
RERANK = "din-rerank.rerank"


@pytest.fixture(scope="module")
def svc():
    spec = dataclasses.replace(get_scenario("din-rerank"), shed=False,
                               seed=0)
    return MultiScenarioService(MultiServiceConfig(scenarios=(spec,)))


def _rerank_visit(ev):
    """(phase spans, exec span) of the event's model-stage visit."""
    spans = [s for s in ev.meta["spans"] if s["stage"] == RERANK]
    ex = [s for s in spans if s["kind"] == "exec"]
    return [s for s in spans if s["kind"] in PHASES], (ex[0] if ex else None)


def test_phase_spans_nest_in_the_model_stage_exec_span(svc):
    rep = AsyncExecutor(svc.plan, tracer=Tracer()).run(
        svc.make_requests(24, seed=11))
    scored = [ev for ev in rep.results if _rerank_visit(ev)[1] is not None]
    assert scored
    pointwise_packs = set()
    for ev in scored:
        phases, ex = _rerank_visit(ev)
        spans = ev.meta["spans"]
        assert spans.index(ex) > max(spans.index(p) for p in phases)
        for p in phases:
            assert ex["t0"] <= p["t0"] <= p["t1"] <= ex["t1"]
            assert p["attrs"]["parent"] == f"{RERANK}:exec"
        by_call = {}
        for p in phases:
            by_call.setdefault(p["attrs"]["call"], []).append(p)
        assert [p["kind"] for p in by_call["pointwise"]] == list(PHASES)
        assert all(p["attrs"]["batch"] == ex["attrs"]["batch"]
                   for p in by_call["pointwise"])
        assert [p["kind"] for p in by_call["candidates"]] == list(PHASES)
        assert all(p["attrs"]["batch"] == 1 for p in by_call["candidates"])
        assert ev.payload["topk"]
        assert sum(p["t1"] - p["t0"] for p in phases) <= \
            ex["t1"] - ex["t0"]
        pointwise_packs.add(id(by_call["pointwise"][0]))
    # a batch's phase is one span shared by its events: once per batch
    assert len(pointwise_packs) == rep.stage_stats[RERANK].batches


def test_untraced_run_records_no_span_and_starts_no_monitor(svc,
                                                            monkeypatch):
    started = []
    monkeypatch.setattr(executors, "StallMonitor",
                        lambda *a, **k: started.append(a))
    rep = AsyncExecutor(svc.plan).run(svc.make_requests(16, seed=12))
    assert not started and rep.process_spans == []
    assert all("spans" not in ev.meta for ev in rep.results)
    assert any("topk" in ev.payload for ev in rep.results)


def test_phases_record_nothing_on_the_virtual_clock(svc):
    tr = Tracer()
    svc.run(n_requests=8, executor="sim", tracer=tr)
    for rec in tr.buffer.traces():
        assert {k for _, k in span_topology(rec)} <= {
            "queue", "assemble", "exec"}
    ev = Event(payload={})
    Tracer().begin(ev, 0.0)
    ev.meta["spans"].append({"stage": "s", "kind": "exec", "t0": 0.0,
                             "t1": 0.0, "attrs": {}})
    # traced, but not on a bound worker thread; and untraced
    assert phase([ev], "pack") is phase([Event(payload={})], "pack")


def test_scores_and_topk_identical_traced_and_untraced(svc):
    rep = AsyncExecutor(svc.plan).run(svc.make_requests(16, seed=13))
    payloads = [ev.payload for ev in rep.results
                if "cube_rows" in ev.payload][:8]
    assert payloads
    op = stage_of(svc.plan.stages[RERANK].op).op

    def serve(traced):
        batch = [Event(payload=p.copy()) for p in payloads]
        if not traced:
            return op(batch, None)
        tr = Tracer()
        for ev in batch:
            tr.begin(ev, time.monotonic())
        tr.bind_thread()
        try:
            tr.exec_begin(batch, RERANK, time.monotonic())
            out = op(batch, None)
            tr.exec_end(batch, RERANK, time.monotonic())
        finally:
            tr.unbind_thread()
        assert all(len(_rerank_visit(ev)[0]) == 8 for ev in batch)
        return out

    plain, traced = serve(False), serve(True)
    for a, b in zip(plain, traced):
        assert a.payload["score"] == b.payload["score"]
        assert a.payload["topk"] == b.payload["topk"]


class _FakeTime:
    """A clock that a sleep advances, each sleep late by the next of
    ``late`` seconds."""

    def __init__(self, late):
        self.t = 100.0
        self.late = list(late)

    def clock(self):
        return self.t

    def sleep(self, s):
        self.t += s + (self.late.pop(0) if self.late else 0.0)


def test_stall_monitor_records_a_late_tick_with_the_open_phase():
    tr = Tracer()
    ready, done = threading.Event(), threading.Event()

    def worker():
        tr.bind_thread()
        ev = Event(payload={})
        tr.begin(ev, 0.0)
        tr.exec_begin([ev], "s", 0.0)
        with phase([ev], "wait"):
            ready.set()
            done.wait(10)
        tr.unbind_thread()

    th = threading.Thread(target=worker, name="sedp:s:0")
    th.start()
    try:
        assert ready.wait(10)
        ft = _FakeTime([0.001, 0.049, 0.2, 0.0])
        mon = StallMonitor(tr, clock=ft.clock, sleep=ft.sleep)
        due0 = ft.t + mon.TICK_S
        for _ in range(4):
            mon.tick()
    finally:
        done.set()
        th.join(10)
    assert not th.is_alive()
    assert len(mon.spans) == 1                 # 1 and 49 ms late: no stall
    sp = mon.spans[0]
    assert tr.process_spans == [sp]
    assert (sp["stage"], sp["kind"]) == ("process", "stall")
    # the third tick was due after two ticks of 5 ms, 1 and 49 ms late
    assert sp["t0"] == pytest.approx(due0 + 0.001 + 0.049 + 2 * 0.005)
    assert sp["t1"] - sp["t0"] == pytest.approx(0.2)
    a = sp["attrs"]
    assert {"gc", "throttled", "majflt", "minflt", "nivcsw", "cpu_ms",
            "threads"} <= set(a)
    assert a["throttled"] is None or set(a["throttled"]) == {
        "nr_throttled", "throttled_usec"}
    w = a["threads"]["sedp:s:0"]
    assert (w["stage"], w["phase"]) == ("s", "wait")
    # no program frame on that thread: its innermost frame, the wait
    assert "threading.py:" in w["frame"] and w["frame"].endswith(" wait")
    assert threading.current_thread().name not in a["threads"]


def _hold_gil(min_s=0.15):
    """Sort random floats in one C call, which holds the interpreter lock
    throughout, until one sort took at least ``min_s``. Returns its
    (start, end) on the monotonic clock."""
    n = 400_000
    while True:
        xs = [random.random() for _ in range(n)]
        t0 = time.monotonic()
        xs.sort()
        t1 = time.monotonic()
        if t1 - t0 >= min_s or n >= 8_000_000:
            return t0, t1
        n *= 2


def test_stall_monitor_records_a_gil_holding_c_call():
    tr = Tracer()
    mon = StallMonitor(tr).start()
    try:
        time.sleep(0.02)
        t0, t1 = _hold_gil()
        time.sleep(0.02)
    finally:
        mon.stop()
    assert not mon._thread.is_alive()
    hit = [s for s in mon.spans if s["t0"] < t1 and s["t1"] > t0]
    assert hit and max(s["t1"] - s["t0"] for s in hit) >= 0.05
    # the sorting thread ran on the CPU through the stall
    assert sum(s["attrs"]["cpu_ms"] for s in hit) >= 25
    assert "MainThread" in hit[0]["attrs"]["threads"]


def test_traced_run_reports_stalls_and_names_workers_after_stages():
    names = set()

    def op(batch, ctx):
        names.add(threading.current_thread().name)
        if any(ev.payload["i"] == 0 for ev in batch):
            _hold_gil()
        return batch

    g = SEDP()
    g.add_stage("a", op, batch_size=1, parallelism=2)
    g.add_stage("b", lambda b, c: b, batch_size=4)
    g.chain("a", "b")
    rep = AsyncExecutor(g.compile(), tracer=Tracer()).run(
        [Event(payload={"i": i}) for i in range(4)])
    assert len(rep.results) == 4
    assert names <= {"sedp:a:0", "sedp:a:1"} and names
    stalls = [s for s in rep.process_spans
              if any(t.get("stage") == "a"
                     for t in s["attrs"]["threads"].values())]
    assert stalls and all(s["kind"] == "stall" for s in rep.process_spans)


def test_process_spans_round_trip_through_chrome(tmp_path):
    tr = Tracer()
    AsyncExecutor(_two_stages(), tracer=tr).run(
        [Event(payload={}) for _ in range(3)])
    ft = _FakeTime([0.3])
    mon = StallMonitor(tr, clock=ft.clock, sleep=ft.sleep)
    mon.tick()
    path = str(tmp_path / "trace.json")
    doc = tr.buffer.export_chrome(path)
    for src in (doc, path):
        back = TraceBuffer.process_from_chrome(src)
        assert len(back) == len(tr.process_spans) >= 1
        for o, b in zip(sorted(tr.process_spans, key=lambda s: s["t0"]),
                        back):
            assert (b["stage"], b["kind"]) == (o["stage"], o["kind"])
            assert b["t0"] == pytest.approx(o["t0"], abs=1e-6)
            assert b["t1"] == pytest.approx(o["t1"], abs=1e-6)
            assert b["attrs"] == o["attrs"]
        # the request records are what they were
        assert len(TraceBuffer.from_chrome(src)) == 3


def _two_stages():
    g = SEDP()
    g.add_stage("a", lambda b, c: b, batch_size=2)
    g.add_stage("b", lambda b, c: b, batch_size=2)
    g.chain("a", "b")
    return g.compile()


def test_critical_path_counts_only_top_level_spans_as_covered():
    def sp(stage, kind, t0, t1):
        return {"stage": stage, "kind": kind, "t0": t0, "t1": t1,
                "attrs": {}}
    rec = {"born_at": 0.0, "done_at": 0.015, "latency_s": 0.015,
           "spans": [sp("a", "queue", 0.0, 0.001),
                     sp("a", "assemble", 0.001, 0.002),
                     sp("a", "pack", 0.002, 0.005),
                     sp("a", "launch", 0.005, 0.007),
                     sp("cube", "shard_fanout", 0.007, 0.011),
                     sp("shard_0", "shard_fetch", 0.007, 0.011),
                     sp("a", "exec", 0.002, 0.012)]}
    cp = critical_path(rec)
    assert cp["unattributed_s"] == pytest.approx(0.015 - 0.012)
    segs = {(s["stage"], s["kind"]): s["dur_s"] for s in cp["segments"]}
    assert segs[("a", "pack")] == pytest.approx(0.003)
    assert segs[("shard_0", "shard_fetch")] == pytest.approx(0.004)
    assert np.isclose(sum(segs.values()), 0.012 + 0.003 + 0.002 + 0.008)
