#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload din.steady --seed 7 --seconds 30 --trace 0

Builds the program's service for the cell's configuration at published
widths, installs weights made from ``--seed``, warms every shape the
cell's traffic reaches (set-up), then offers the traffic open-loop for
``--seconds`` and prints, as its last line, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
``breakdown`` (traced runs) and last ``checks``: each number compared with
the reference beside its limit. The same checks close standard error.

It needs the chips the cell asks for and exits nonzero, printing no
result, where JAX finds fewer (or no TPU). It reads ``BENCHMARK.json``
and the files under ``bench/``; the program comes from ``src/``. JAX's
compilation cache lives in ``bench/.cache/jax`` of the checkout.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path


def _process_start() -> float:
    """This process's start on the monotonic clock (from /proc where it
    can be read; the first line of this script otherwise)."""
    now = time.monotonic()
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19]) / ticks
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return now - max(0.0, uptime - start)
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from jzb.harness import run
    out = run(args.workload, args.seed, args.seconds, bool(args.trace),
              T_PROCESS)
    checks = out["checks"]
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
