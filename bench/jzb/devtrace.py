"""From a JAX profiler trace to device metrics.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the reduction needs, as plain JSON: every device op and program
(module) interval of the device planes, and the host spans the benchmark
wrapped around each SEDP stage's op (``TraceAnnotation`` named after the
stage). ``reduce`` turns that into the busy union, the idle share, device
time by program and by op, kernel time by stable name, and idle gaps
blamed on the host stage that was running in them.

All times are nanoseconds on the trace's clock, which the profiler shares
between host and device events.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict

_KEEP_STATS = ("hlo_module", "hlo_op", "long_name", "hlo_category",
               "tf_op", "program_id")


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        if k in _KEEP_STATS or k == "n":
            out[k] = v if isinstance(v, (int, float, str)) else str(v)
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def trim(doc: dict, max_events: int) -> dict:
    """The first ``max_events`` events of every line (a small recorded
    trace for the tests)."""
    return {"devices": {p: {ln: evs[:max_events] for ln, evs in lines.items()}
                        for p, lines in doc["devices"].items()},
            "host": doc["host"][:max_events]}


def extract(path: str, host_names) -> dict:
    """Device planes' lines and the named host spans, as JSON lists of
    ``[name, start_ns, dur_ns, stats]``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host_names = set(host_names)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [[e.name, e.start_ns, e.duration_ns,
                                     _stats(e)] for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_names:
                        host.append([e.name, e.start_ns, e.duration_ns,
                                     _stats(e)])
    return {"devices": devices, "host": host}


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_lines(lines: dict) -> list:
    """The op-level events of one device plane: its ``XLA Ops`` and
    ``Async XLA Ops`` lines (the latter holds the DMA copies)."""
    return lines.get("XLA Ops", []) + lines.get("Async XLA Ops", [])


def op_name(ev) -> str:
    """A stable name for a device op: its program, then its HLO op name
    without the numeric suffix XLA adds (an event named by its whole HLO
    instruction text is named by the instruction)."""
    st = ev[3]
    mod = re.sub(r"\(\d+\)$", "", str(st.get("hlo_module", "")))
    name = str(st.get("hlo_op") or ev[0])
    m = re.search(r"%([\w.-]+)", name)
    if m and (" " in name or "=" in name):
        name = m.group(1)
    base = re.sub(r"[.\d]+$", "", name) or name
    return f"{mod}/{base}" if mod else base


def module_name(ev) -> str:
    return re.sub(r"\(\d+\)$", "", ev[0])


def reduce(doc: dict, window_ns: tuple, kernels: dict | None = None,
           modules: tuple = ()) -> dict:
    """``window_ns``: the traced window on the trace clock. ``kernels``:
    kernel name → the HLO instruction names of its ops. ``modules``:
    prefixes of the program names whose device time is summed as
    ``module_s``. Times are averaged over the device planes; idle gaps
    are blamed on ``doc["host"]`` spans."""
    t0, t1 = window_ns
    planes = [lines for lines in doc["devices"].values()
              if "XLA Ops" in lines]
    if not planes:
        raise ValueError("the trace holds no device plane with XLA ops")
    busy = 0.0
    by_op: dict = defaultdict(float)
    kernel_s: dict = defaultdict(float)
    kernel_n: dict = defaultdict(int)
    module_s = 0.0
    gaps: dict = defaultdict(float)
    host = sorted((h[1], h[1] + h[2], h[0]) for h in doc["host"])
    host_starts = [h[0] for h in host]
    longest = max((h[1] - h[0] for h in host), default=0)
    for lines in planes:
        ops = [e for e in _op_lines(lines)
               if e[1] < t1 and e[1] + e[2] > t0]
        spans = _union((max(e[1], t0), min(e[1] + e[2], t1)) for e in ops)
        busy += sum(e - s for s, e in spans) / 1e9
        for e in ops:
            name = op_name(e)
            by_op[name] += e[2] / 1e9
            for k, names in (kernels or {}).items():
                if name.rsplit("/", 1)[-1] in names:
                    kernel_s[k] += e[2] / 1e9
                    kernel_n[k] += 1
        for e in lines.get("XLA Modules", []):
            if e[1] < t1 and e[1] + e[2] > t0 and \
                    module_name(e).startswith(modules or ("",)):
                module_s += (min(e[1] + e[2], t1) - max(e[1], t0)) / 1e9
        edges = [t0] + [x for s, e in spans for x in (s, e)] + [t1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps[_blame(host, host_starts, longest, s, e)] += \
                    (e - s) / 1e9
    n = len(planes)
    window_s = (t1 - t0) / 1e9
    return {
        "busy_s": busy / n, "window_s": window_s,
        "idle_share": 1.0 - busy / n / window_s,
        "module_s": module_s / n,
        "kernel_s": {k: v / n for k, v in kernel_s.items()},
        "kernel_calls": dict(kernel_n),
        "device_ops": sorted(([k, v / n] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v / n] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:10],
    }


def _blame(host: list, starts: list, longest: float, s: float,
           e: float) -> str:
    """The host stage whose spans overlap [s, e) the most, or ``host
    idle`` where no stage op was running."""
    cover: dict = defaultdict(float)
    lo = bisect.bisect_left(starts, s - longest)
    hi = bisect.bisect_left(starts, e)
    for hs, he, name in host[lo:hi]:
        ov = min(he, e) - max(hs, s)
        if ov > 0:
            cover[name] += ov
    return max(cover, key=cover.get) if cover else "host idle"

