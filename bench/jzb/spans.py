"""Readers of the program's own spans beyond the executor's three: the
phases of a stage op (``obs.trace.phase``) and the stall monitor's
process spans (``obs.trace.StallMonitor``). A program that records
neither reads as None, not as zero."""
from __future__ import annotations


def phase_ms(w, short: str, kind: str) -> float | None:
    """Time of a stage's ``kind`` phases per request the stage handled, in
    ms: each span once (the events of a batch share one span), over
    ``StageStats.events``, the denominator of ``exec_ms.<stage>``."""
    st = w.stage(short)
    if not st or not st.events:
        return None
    spans = {id(s): s for i in range(len(w.events))
             for s in w.spans(i, short)
             if s["kind"] == kind and "parent" in s["attrs"]}
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans.values()) / st.events


def stall_share(w) -> float | None:
    """Share of the run, first due time to the end of the drain, that the
    process spent in ``process:stall`` spans, in %; None where the program
    keeps no process spans."""
    spans = getattr(w.report, "process_spans", None)
    if spans is None:
        return None
    t0, t1 = float(w.due[0]), float(w.t_end)
    stalled = sum(max(0.0, min(s["t1"], t1) - max(s["t0"], t0))
                  for s in spans if s["kind"] == "stall")
    return 100.0 * stalled / (t1 - t0)
