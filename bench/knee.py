#!/usr/bin/env python3
"""The knee of a rate sweep, and the cell rate at a share of it.

    python bench/knee.py SWEEP.jsonl [--share 0.8] [--write TRAFFIC.json]

Reads the ``"kind": "sweep"`` lines ``bench/calibrate.py`` wrote. The knee
is the highest swept rate at which the system kept up, as at every rate
below it: goodput within 1% of the offered rate, at most 1% of requests
timed out, and the backlog drained within a second of the last due time.
Prints the knee, the rate (``share`` of it, rounded down) and the p99 at
the swept rate nearest below that; ``--write`` stores rate and knee in a
traffic file.
"""
from __future__ import annotations

import argparse
import json
import math
from pathlib import Path


def knee(sweep: list) -> float | None:
    best = None
    for r in sorted(sweep, key=lambda r: r["rate_rps"]):
        kept_up = (r["goodput_rps"] >= 0.99 * r["rate_rps"]
                   and r["timed_out_share"] <= 0.01 and r["drain_s"] <= 1.0)
        if not kept_up:
            break
        best = r["rate_rps"]
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("sweep")
    ap.add_argument("--share", type=float, default=0.8)
    ap.add_argument("--write", default=None)
    args = ap.parse_args(argv)
    sweep = [json.loads(line) for line in Path(args.sweep).read_text()
             .splitlines() if line.startswith("{")]
    sweep = [r for r in sweep if r.get("kind") == "sweep"]
    k = knee(sweep)
    if k is None:
        raise SystemExit("no swept rate was sustained")
    rate = math.floor(args.share * k)
    below = [r for r in sweep if r["rate_rps"] <= rate]
    p99 = max(below, key=lambda r: r["rate_rps"])["p99_ms"] if below else None
    print(json.dumps({"knee_rps": k, "rate_rps": rate,
                      "p99_ms_near_rate": p99}))
    if args.write:
        path = Path(args.write)
        mix = json.loads(path.read_text())
        mix.update(rate_rps=rate, knee_rps=k)
        path.write_text(json.dumps(mix, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
