#!/usr/bin/env python3
"""Readings for a cell's rate and limits, many windows in one process.

    python bench/calibrate.py --workload din.steady --seconds 10 \\
        --rates 200,400,800 --seeds 1,2,3 --control 1,2,3 --out FILE

Set-up is paid once. Then, in order: one window per ``--rates`` entry
(the cell's traffic at that rate: latency, goodput, timed-out share,
kept candidates, the model stage's deepest queue: the knee sweep); one
traced window at the cell's rate (``--trace-seed``); and one window per
``--seeds`` entry at the cell's rate, each with that seed's weights and
traffic, compared with the reference (the program's readings) and, for
the seeds in ``--control``, the control's readings. The benchmark's own
runs (``run.py``) never run the control. One JSON line per window goes to
standard output and to ``--out``.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

T_PROCESS = time.monotonic()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--trace-seed", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from jzb import check
    from jzb.harness import N_SAMPLE, Cell, log, percentile
    cell = Cell(args.workload)
    seeds, control = _ints(args.seeds), set(_ints(args.control))
    cell.build()
    cell.install_weights(seeds[0] if seeds else 1)
    tr, _ = cell.make_events(seeds[0] if seeds else 1, args.seconds)
    cell.warm(tr)
    log(f"set-up {time.monotonic() - T_PROCESS:.3f} s: " + " ".join(
        f"{k}={v:.3f}" for k, v in cell.parts.items()))
    out = open(args.out, "a") if args.out else None
    base = dict(cell.traffic)

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def summary(w, **extra) -> dict:
        lat = w.latency_s * 1e3
        st = w.stage("rerank")
        sh = cell.shedder_state
        timed_out = sum(1 for ev in w.events
                        if ev.meta["response"].timed_out)
        rec = dict(extra, n=len(w.events), p50_ms=percentile(lat, 0.5),
                   p95_ms=percentile(lat, 0.95),
                   p99_ms=percentile(lat, 0.99),
                   goodput_rps=float(w.ok.sum()) / w.seconds,
                   timed_out_share=timed_out / len(w.events),
                   kept_share=(sh.kept_events / max(1, sh.kept_events
                                                    + sh.shed_events)),
                   rerank_max_depth=st.max_depth if st else None,
                   rerank_exec_ms=1e3 * st.busy_s / max(1, st.events)
                   if st else None,
                   drain_s=w.t_end - w.due[-1],
                   gen_late_p99_ms=percentile((w.release - w.due) * 1e3,
                                              0.99),
                   window_compiles=cell.window_compiles,
                   peak_bytes=cell.peak_bytes())
        return rec

    for rate in _ints(args.rates):
        cell.traffic = dict(base, rate_rps=rate)
        tr, events = cell.make_events(1000 + rate, args.seconds)
        w = cell.window(tr, events, args.seconds, trace=False)
        emit(summary(w, kind="sweep", rate_rps=rate))
    cell.traffic = base
    if args.trace_seed is not None:
        tr, events = cell.make_events(args.trace_seed, args.seconds)
        w = cell.window(tr, events, args.seconds, trace=True)
        rec = summary(w, kind="trace", seed=args.trace_seed)
        rec["result"] = cell.result(w, True, T_PROCESS, T_PROCESS,
                                    cell.peak_bytes(), {}, True)
        emit(rec)
    for seed in seeds:
        cell.install_weights(seed)
        tr, events = cell.make_events(seed, args.seconds)
        w = cell.window(tr, events, args.seconds, trace=False)
        rec = summary(w, kind="seed", seed=seed)
        rec["program"] = check.compare(w, cell.params, seed, N_SAMPLE)
        if seed in control:
            rec["control"] = check.compare(w, cell.params, seed, N_SAMPLE,
                                           control=True)
        emit(rec)
    cell.free_program()
    return 0


if __name__ == "__main__":
    sys.exit(main())
