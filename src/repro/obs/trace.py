"""Per-request tracing for the SEDP loop (DESIGN.md §10.1).

A ``Tracer`` threads a trace id + span list through ``Event.meta`` on both
executors. Every stage visit records three spans — ``queue`` (channel
enqueue → dequeue, including backpressure stall on the async executor),
``assemble`` (dequeue → micro-batch dispatch), ``exec`` (op start → op
end) — so the span topology is identical Sim-vs-Async even though the
durations come from different clocks (virtual vs wall).

Stages annotate the OPEN span via ``annotate(ev, cache_hit=True, ...)``;
the call is a no-op (one dict lookup) on untraced events, which is what
keeps the telemetry-OFF path free. Below the exec span, ``phase`` times a
named phase of the op (the model stage's pack, launch, wait and post) as a
child span, and on the profiler's trace too; it records on the wall-clock
executor's workers only.

``StallMonitor`` watches the whole process during a traced
``AsyncExecutor`` run: a ``process:stall`` span for every stretch of 50 ms
or more in which its 5 ms tick could not run, with the GC passes, cgroup
CPU throttling, page faults and involuntary switches charged in it, and
what each thread had open. Such process spans belong to no request.

``TraceBuffer`` bounds memory with tail-based sampling: errors, deadline
expiries, shed-dropped and degraded(>0) traces are ALWAYS kept (up to a
cap), plus a top-K latency heap and a recent ring for baseline context.
Export is Chrome trace-event JSON (load in Perfetto / chrome://tracing);
``from_chrome`` round-trips it and ``critical_path`` attributes a
request's latency to stages/queues from the exported form alone.
"""
from __future__ import annotations

import contextlib
import gc
import heapq
import itertools
import json
import os
import resource
import sys
import threading
import time
from collections import deque
from pathlib import Path
from typing import Optional

# The spans the executor records for every stage visit; every other kind
# (a phase of the op, the mesh's shard fan-out) nests inside an exec span.
TOP_LEVEL_KINDS = ("queue", "assemble", "exec")
# Chrome export: requests are pid 1 (tid = trace id), process spans pid 2.
_REQUEST_PID, _PROCESS_PID = 1, 2

# The calling AsyncExecutor worker's slot, ``[stage, phase]``, while a
# traced run binds it (``Tracer.bind_thread``): phases record only there.
_worker = threading.local()
_NO_PHASE = contextlib.nullcontext()
_PROGRAM_DIR = str(Path(__file__).resolve().parents[1]) + os.sep


def annotate(ev, **attrs) -> None:
    """Merge attributes into the event's currently-open span. No-op when
    the event is untraced (the hot-path cost when telemetry is off)."""
    spans = ev.meta.get("spans")
    if spans:
        spans[-1]["attrs"].update(attrs)


def add_child_spans(ev, child_spans) -> None:
    """Attach pre-built child spans (e.g. the mesh's per-shard
    ``shard_fetch`` sub-batches) to a traced event's current stage visit.

    Children are inserted BEFORE the currently-open ``exec`` span rather
    than appended: ``Tracer.exec_end`` closes ``spans[-1]`` only if it is
    the exec span, and ``annotate`` targets ``spans[-1]`` — appending
    would orphan the stage's own span. No-op on untraced events."""
    spans = ev.meta.get("spans")
    if not spans or not child_spans:
        return
    if spans[-1]["kind"] == "exec":
        spans[-1:-1] = child_spans
    else:
        spans.extend(child_spans)


def phase(batch, name: str, **attrs):
    """Time the ``with`` body as phase ``name`` of the running stage op: a
    child span of the batch's open exec span, inserted before it (as
    ``add_child_spans`` inserts) on every traced event of the batch.

    The one span dict is shared by the events (``attrs["batch"]`` of
    them), so a reader counts it once per batch; ``attrs["parent"]`` names
    the exec span. While it is open the worker's slot names the phase, and
    ``jax.profiler.TraceAnnotation("<stage>/<name>")`` puts it on the
    device trace's clock. Phases are wall-clock spans: off an
    ``AsyncExecutor`` worker bound by a traced run (``SimExecutor``'s
    virtual clock, or no tracer) this is one ``meta.get`` and a shared
    no-op."""
    spans = batch[0].meta.get("spans")
    if not spans or spans[-1]["kind"] != "exec":
        return _NO_PHASE
    slot = getattr(_worker, "slot", None)
    if slot is None:
        return _NO_PHASE
    return _Phase(batch, spans[-1]["stage"], name, attrs, slot)


class _Phase:
    __slots__ = ("batch", "span", "slot", "prev", "ann")

    def __init__(self, batch, stage: str, name: str, attrs: dict,
                 slot: list):
        from jax.profiler import TraceAnnotation
        self.batch = batch
        self.slot = slot
        self.span = {"stage": stage, "kind": name, "t0": 0.0, "t1": 0.0,
                     "attrs": dict(attrs, parent=f"{stage}:exec",
                                   batch=len(batch))}
        self.ann = TraceAnnotation(f"{stage}/{name}", n=len(batch), **attrs)

    def __enter__(self):
        sp = self.span
        for ev in self.batch:
            add_child_spans(ev, [sp])
        self.prev, self.slot[1] = self.slot[1], sp["kind"]
        self.ann.__enter__()
        sp["t0"] = sp["t1"] = time.monotonic()
        return sp

    def __exit__(self, *exc):
        self.span["t1"] = time.monotonic()
        self.ann.__exit__(*exc)
        self.slot[1] = self.prev
        return False


def shard_fanout_spans(fanout: list) -> list:
    """Build the ``shard_fanout`` span family from a MeshCube fan-out
    record list (``take_fanout()``): one ``cube:shard_fanout`` parent
    covering the scatter/gather envelope plus one ``shard_<s>:shard_fetch``
    child per sub-batch. The spans travel through Chrome export like any
    other (kind rides in the ``stage:kind`` name), so ``critical_path`` /
    ``shard_profile`` attribute tail latency to the slowest shard from an
    exported trace alone."""
    if not fanout:
        return []
    t0 = min(f["t0"] for f in fanout)
    t1 = max(f["t1"] for f in fanout)
    spans = [{"stage": "cube", "kind": "shard_fanout", "t0": t0, "t1": t1,
              "attrs": {"n_shards": len(fanout)}}]
    for f in fanout:
        spans.append({"stage": f"shard_{f['shard']}", "kind": "shard_fetch",
                      "t0": f["t0"], "t1": f["t1"],
                      "attrs": {"shard": f["shard"], "host": f["host"],
                                "n_keys": f["n_keys"],
                                "hedged": f["hedged"],
                                "failed": f["failed"]}})
    return spans


def _status_of(ev) -> str:
    if ev.meta.get("error"):
        return "error"
    if ev.meta.get("timed_out"):
        return "expired"
    return "ok"


class Tracer:
    """Executor-side hook set. All methods tolerate untraced events (an
    executor may run a mix when fanout clones predate the tracer)."""

    def __init__(self, buffer: Optional["TraceBuffer"] = None):
        self.buffer = buffer if buffer is not None else TraceBuffer()
        self._ids = itertools.count(1)
        self._slots: dict[int, list] = {}       # thread ident → its slot
        self._slots_lock = threading.Lock()

    @property
    def process_spans(self) -> list:
        """Spans of the whole process (``process:stall``), no request's."""
        return self.buffer.process_spans()

    # ---------------------------------------------------- worker slots

    def bind_thread(self) -> None:
        """Give the calling worker thread a slot, ``[stage, phase]``, that
        ``exec_begin``/``exec_end`` and ``phase`` keep and the stall
        monitor reads. Phases record only on a bound thread."""
        _worker.slot = slot = [None, None]
        with self._slots_lock:
            self._slots[threading.get_ident()] = slot

    def unbind_thread(self) -> None:
        _worker.slot = None
        with self._slots_lock:
            self._slots.pop(threading.get_ident(), None)

    def open_slots(self) -> dict:
        """Thread ident → (stage, phase) each bound worker has open."""
        with self._slots_lock:
            return {t: tuple(slot) for t, slot in self._slots.items()}

    # ------------------------------------------------------------ hooks

    def begin(self, ev, t: float) -> None:
        if "trace_id" not in ev.meta:
            ev.meta["trace_id"] = next(self._ids)
            ev.meta["spans"] = []

    def adopt(self, parent_ev, clone_ev) -> None:
        """Fanout clones share the parent's trace id and inherit a copy of
        the span history up to the fork (the closed prefix is shared
        structurally; each branch appends to its own list)."""
        spans = parent_ev.meta.get("spans")
        if spans is None:
            return
        clone_ev.meta["trace_id"] = parent_ev.meta["trace_id"]
        clone_ev.meta["spans"] = list(spans)

    def enqueued(self, ev, stage: str, t: float) -> None:
        spans = ev.meta.get("spans")
        if spans is not None:
            spans.append({"stage": stage, "kind": "queue",
                          "t0": t, "t1": t, "attrs": {}})

    def dequeued(self, ev, stage: str, t: float) -> None:
        spans = ev.meta.get("spans")
        if spans is not None:
            if spans and spans[-1]["kind"] == "queue":
                spans[-1]["t1"] = t
            spans.append({"stage": stage, "kind": "assemble",
                          "t0": t, "t1": t, "attrs": {}})

    def exec_begin(self, batch, stage: str, t: float) -> None:
        for ev in batch:
            spans = ev.meta.get("spans")
            if spans is not None:
                if spans and spans[-1]["kind"] == "assemble":
                    spans[-1]["t1"] = t
                spans.append({"stage": stage, "kind": "exec",
                              "t0": t, "t1": t,
                              "attrs": {"batch": len(batch)}})
        slot = getattr(_worker, "slot", None)
        if slot is not None:
            slot[0] = stage

    def exec_end(self, batch, stage: str, t: float, **attrs) -> None:
        for ev in batch:
            spans = ev.meta.get("spans")
            if spans is not None and spans and spans[-1]["kind"] == "exec":
                spans[-1]["t1"] = t
                if attrs:
                    spans[-1]["attrs"].update(attrs)
        slot = getattr(_worker, "slot", None)
        if slot is not None:
            slot[0] = slot[1] = None

    def expired(self, ev, stage: str, t: float) -> None:
        """Deadline gate fired at dispatch: close whatever span is open
        and mark the expiry decision on it."""
        spans = ev.meta.get("spans")
        if spans is not None and spans:
            spans[-1]["t1"] = t
            spans[-1]["attrs"]["expired"] = True

    def dropped(self, ev, stage: str, t: float) -> None:
        """Overflow-policy drop at a bounded channel: the request sheds
        before its queue span ever opened."""
        spans = ev.meta.get("spans")
        if spans is not None:
            spans.append({"stage": stage, "kind": "queue", "t0": t, "t1": t,
                          "attrs": {"dropped": True}})
        self.finish(ev, t, status="dropped")

    def finish(self, ev, t: float, status: Optional[str] = None) -> None:
        spans = ev.meta.get("spans")
        if spans is None:
            return
        payload = ev.payload
        tier = (payload.get("degraded_tier", 0)
                if hasattr(payload, "get") else 0) or 0
        rec = {
            "trace_id": ev.meta["trace_id"],
            "req_id": ev.req_id,
            "born_at": ev.born_at,
            "done_at": t,
            "latency_s": max(0.0, t - ev.born_at),
            "status": status or _status_of(ev),
            "degraded_tier": int(tier),
            "spans": spans,
        }
        if ev.meta.get("error"):
            rec["error"] = ev.meta["error"]
        self.buffer.add(rec)


class TraceBuffer:
    """Bounded trace store with tail-based sampling.

    Three compartments: ``flagged`` (errors / expired / dropped /
    degraded>0 — the traces an operator actually pages through),
    ``top`` (K slowest OK traces), ``recent`` (ring of the latest OK
    traces for baseline comparison). Each is individually bounded, so
    total memory is O(max_flagged + max_top + max_recent)."""

    def __init__(self, max_flagged: int = 512, max_top: int = 64,
                 max_recent: int = 256):
        self.max_top = max_top
        self._flagged: deque = deque(maxlen=max_flagged)
        self._top: list = []                       # min-heap (latency, seq, rec)
        self._recent: deque = deque(maxlen=max_recent)
        self._process: deque = deque(maxlen=max_flagged)    # newest kept
        self._seq = itertools.count()
        self._lock = threading.Lock()
        self.added = 0          # every record offered
        self.flagged_total = 0  # records that hit the always-keep rules

    def add(self, rec: dict) -> None:
        with self._lock:
            self.added += 1
            if rec["status"] != "ok" or rec["degraded_tier"] > 0:
                self.flagged_total += 1
                self._flagged.append(rec)
                return
            self._recent.append(rec)
            item = (rec["latency_s"], next(self._seq), rec)
            if len(self._top) < self.max_top:
                heapq.heappush(self._top, item)
            elif item[0] > self._top[0][0]:
                heapq.heapreplace(self._top, item)

    def traces(self) -> list[dict]:
        """All retained traces, deduped (a top-K trace may also sit in the
        recent ring), ordered by completion time."""
        with self._lock:
            seen: set[int] = set()
            out: list[dict] = []
            for rec in itertools.chain(self._flagged,
                                       (r for _, _, r in self._top),
                                       self._recent):
                if id(rec) not in seen:
                    seen.add(id(rec))
                    out.append(rec)
        out.sort(key=lambda r: (r["done_at"], r["trace_id"]))
        return out

    def add_process_span(self, span: dict) -> None:
        with self._lock:
            self._process.append(span)

    def process_spans(self) -> list[dict]:
        with self._lock:
            return list(self._process)

    def find(self, **conds) -> list[dict]:
        """Filter retained traces by top-level record fields
        (``find(status="expired")``, ``find(trace_id=7)``)."""
        return [r for r in self.traces()
                if all(r.get(k) == v for k, v in conds.items())]

    def clear(self) -> None:
        with self._lock:
            self._flagged.clear()
            self._top = []
            self._recent.clear()
            self._process.clear()

    # ----------------------------------------------------------- export

    def export_chrome(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON: one ``X`` (complete) event per span plus
        a per-request summary event carrying status/degraded_tier — enough
        to reconstruct each trace with ``from_chrome``. Process spans go on
        a track of their own (pid 2), read back by
        ``process_from_chrome``."""
        events = []
        for rec in self.traces():
            tid = rec["trace_id"]
            events.append({
                "name": "request", "cat": "request", "ph": "X",
                "ts": rec["born_at"] * 1e6,
                "dur": max(0.0, rec["done_at"] - rec["born_at"]) * 1e6,
                "pid": _REQUEST_PID, "tid": tid,
                "args": {"status": rec["status"],
                         "degraded_tier": rec["degraded_tier"],
                         "req_id": rec["req_id"]},
            })
            for sp in rec["spans"]:
                events.append({
                    "name": f'{sp["stage"]}:{sp["kind"]}',
                    "cat": sp["kind"], "ph": "X",
                    "ts": sp["t0"] * 1e6,
                    "dur": max(0.0, sp["t1"] - sp["t0"]) * 1e6,
                    "pid": _REQUEST_PID, "tid": tid,
                    "args": dict(sp["attrs"]),
                })
        for sp in self.process_spans():
            events.append({
                "name": f'{sp["stage"]}:{sp["kind"]}', "cat": sp["kind"],
                "ph": "X", "ts": sp["t0"] * 1e6,
                "dur": max(0.0, sp["t1"] - sp["t0"]) * 1e6,
                "pid": _PROCESS_PID, "tid": 0, "args": dict(sp["attrs"]),
            })
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if path is not None:
            with open(path, "w") as f:
                json.dump(doc, f)
        return doc

    @staticmethod
    def from_chrome(doc) -> list[dict]:
        """Rebuild trace records from an exported Chrome trace document
        (dict, JSON string, or path). The analyzer functions below accept
        these reconstructed records — the acceptance drill reads the
        request path back from the export alone."""
        by_tid: dict[int, dict] = {}
        for e in _chrome_events(doc):
            if e.get("pid") == _PROCESS_PID:
                continue
            tid = e["tid"]
            rec = by_tid.setdefault(tid, {"trace_id": tid, "spans": []})
            t0 = e["ts"] / 1e6
            t1 = t0 + e.get("dur", 0.0) / 1e6
            if e["name"] == "request":
                rec.update(born_at=t0, done_at=t1,
                           latency_s=max(0.0, t1 - t0),
                           status=e["args"].get("status", "ok"),
                           degraded_tier=e["args"].get("degraded_tier", 0),
                           req_id=e["args"].get("req_id"))
            else:
                stage, _, kind = e["name"].rpartition(":")
                rec["spans"].append({"stage": stage, "kind": kind,
                                     "t0": t0, "t1": t1,
                                     "attrs": dict(e.get("args", {}))})
        for rec in by_tid.values():
            rec["spans"].sort(key=lambda s: (s["t0"], s["t1"]))
            rec.setdefault("status", "ok")
            rec.setdefault("degraded_tier", 0)
        return sorted(by_tid.values(), key=lambda r: r["trace_id"])

    @staticmethod
    def process_from_chrome(doc) -> list[dict]:
        """The process spans of an exported document, by start time."""
        out = []
        for e in _chrome_events(doc):
            if e.get("pid") == _PROCESS_PID:
                stage, _, kind = e["name"].rpartition(":")
                t0 = e["ts"] / 1e6
                out.append({"stage": stage, "kind": kind, "t0": t0,
                            "t1": t0 + e.get("dur", 0.0) / 1e6,
                            "attrs": dict(e.get("args", {}))})
        return sorted(out, key=lambda s: s["t0"])


def _chrome_events(doc) -> list:
    """The events of a Chrome trace document (dict, JSON string, or
    path)."""
    if isinstance(doc, str):
        try:
            doc = json.loads(doc)
        except ValueError:
            with open(doc) as f:
                doc = json.load(f)
    return doc.get("traceEvents", [])


# -------------------------------------------------------- stall monitor

def _cpu_stat_paths() -> list:
    """Where the process's CPU cgroup keeps ``cpu.stat``: its own cgroup
    (``/proc/self/cgroup``, v2 or v1), then the roots."""
    out = []
    try:
        lines = Path("/proc/self/cgroup").read_text().splitlines()
    except OSError:
        lines = []
    for ln in lines:
        _, ctrl, path = ln.split(":", 2)
        path = path.rstrip("/")
        if not ctrl:                                    # cgroup v2
            out += [f"/sys/fs/cgroup{path}/cpu.stat",
                    f"/sys/fs/cgroup/unified{path}/cpu.stat"]
        elif "cpu" in ctrl.split(","):                  # v1
            out += [f"/sys/fs/cgroup/{ctrl}{path}/cpu.stat",
                    f"/sys/fs/cgroup/cpu{path}/cpu.stat"]
    return out + ["/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat",
                  "/sys/fs/cgroup/cpu,cpuacct/cpu.stat"]


def _throttling(fd: Optional[int]) -> Optional[tuple]:
    """(nr_throttled, throttled_usec) of the CPU cgroup's ``cpu.stat``
    open at ``fd``; None where it cannot be read or does not count
    throttling."""
    if fd is None:
        return None
    try:
        text = os.pread(fd, 4096, 0).decode()
    except OSError:
        return None
    kv = dict(ln.split(None, 1) for ln in text.splitlines() if " " in ln)
    if "nr_throttled" not in kv:
        return None
    usec = (int(kv["throttled_usec"]) if "throttled_usec" in kv
            else int(kv.get("throttled_time", 0)) // 1000)   # v1: ns
    return int(kv["nr_throttled"]), usec


def _open_cpu_stat() -> Optional[int]:
    for path in _cpu_stat_paths():
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError:
            continue
        if _throttling(fd) is not None:
            return fd
        os.close(fd)
    return None


def _program_frame(frame) -> str:
    """``path:line function`` of the innermost frame in the program's own
    code (the ``repro`` package, path relative to it), else of the
    innermost frame."""
    inner = frame
    while frame is not None and \
            not frame.f_code.co_filename.startswith(_PROGRAM_DIR):
        frame = frame.f_back
    f = frame or inner
    path = f.f_code.co_filename
    if frame is not None:
        path = path[len(_PROGRAM_DIR):]
    return f"{path}:{f.f_lineno} {f.f_code.co_name}"


class StallMonitor:
    """Records whole-process stalls as ``process:stall`` spans on a tracer.

    A daemon thread sleeps ``TICK_S`` at a time. A wake that comes
    ``MIN_STALL_S`` or more after it was due means the process could not
    run it: the interpreter lock was held (a long C call), or the process
    was descheduled, throttled by its cgroup's CPU quota, or faulting pages
    in. The span covers [due, wake], on the monotonic clock, with what the
    process was charged in between (``gc`` passes started; the cgroup's
    ``throttled`` ``{nr_throttled, throttled_usec}``, None where no
    ``cpu.stat`` counts it; ``getrusage`` ``majflt``, ``minflt``,
    ``nivcsw`` and ``cpu_ms``, the CPU time all threads used: about the
    stall's length where one thread held the interpreter lock, near 0
    where the process was frozen or blocked) and, by thread name, each
    thread's innermost program ``frame`` at the wake and, for bound
    workers, the ``stage`` and ``phase`` it had open. ``clock`` and
    ``sleep`` are injectable, and ``tick`` is one step of the thread's
    loop."""

    TICK_S = 0.005
    MIN_STALL_S = 0.05

    def __init__(self, tracer: Tracer, clock=time.monotonic,
                 sleep=time.sleep):
        self.tracer = tracer
        self.clock = clock
        self.sleep = sleep
        self.spans: list[dict] = []         # this monitor's, in order
        self._gc_passes = 0
        self._fd: Optional[int] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._reset()

    def _reset(self) -> None:
        self._before = self._counters()
        self._due = self.clock() + self.TICK_S

    def _on_gc(self, gc_phase, info) -> None:
        if gc_phase == "start":
            self._gc_passes += 1

    def _counters(self) -> tuple:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (self._gc_passes, ru.ru_majflt, ru.ru_minflt, ru.ru_nivcsw,
                _throttling(self._fd), ru.ru_utime + ru.ru_stime)

    def start(self) -> "StallMonitor":
        gc.callbacks.append(self._on_gc)
        self._fd = _open_cpu_stat()
        self._reset()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="sedp:stall-monitor")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None

    def _run(self) -> None:
        while not self._stop.is_set():
            self.tick()

    def tick(self) -> None:
        """Sleep to the due time; record a stall if the wake came late."""
        self.sleep(max(0.0, self._due - self.clock()))
        wake = self.clock()
        after = self._counters()
        if wake - self._due >= self.MIN_STALL_S:
            self._record(self._due, wake, self._before, after)
        self._before = after
        self._due = wake + self.TICK_S

    def _record(self, due: float, wake: float, before: tuple,
                after: tuple) -> None:
        thr = None
        if before[4] is not None and after[4] is not None:
            thr = {"nr_throttled": after[4][0] - before[4][0],
                   "throttled_usec": after[4][1] - before[4][1]}
        span = {"stage": "process", "kind": "stall", "t0": due, "t1": wake,
                "attrs": {"gc": after[0] - before[0], "throttled": thr,
                          "majflt": after[1] - before[1],
                          "minflt": after[2] - before[2],
                          "nivcsw": after[3] - before[3],
                          "cpu_ms": round((after[5] - before[5]) * 1e3, 3),
                          "threads": self._threads()}}
        self.spans.append(span)
        self.tracer.buffer.add_process_span(span)

    def _threads(self) -> dict:
        open_ = self.tracer.open_slots()
        names = {t.ident: t.name for t in threading.enumerate()}
        me = threading.get_ident()
        out = {}
        for ident, frame in sys._current_frames().items():
            if ident == me:
                continue
            entry = {"frame": _program_frame(frame)}
            stage, ph = open_.get(ident, (None, None))
            if stage is not None:
                entry["stage"] = stage
            if ph is not None:
                entry["phase"] = ph
            out[names.get(ident, str(ident))] = entry
        return out


# ------------------------------------------------------------- analysis

def span_topology(rec: dict) -> list[tuple[str, str]]:
    """(stage, kind) sequence — the structural shape of a trace, invariant
    across executors for the same routing decisions."""
    return [(sp["stage"], sp["kind"]) for sp in rec["spans"]]


def stage_path(rec: dict) -> list[str]:
    """The stages a request actually visited, in visit order (one entry
    per stage visit, from the queue spans — present even for visits that
    expired before executing)."""
    return [sp["stage"] for sp in rec["spans"] if sp["kind"] == "queue"]


def critical_path(rec: dict) -> dict:
    """Attribute a request's end-to-end latency to (stage, kind) segments.

    Returns ``{"total_s", "segments": [{stage, kind, dur_s, frac}...],
    "unattributed_s"}`` with segments sorted by descending duration —
    "where did my p99 go" from one trace. Spans nested in an exec span
    (phases, shard fan-out) are segments of their own but count toward
    the covered time only through their exec span."""
    total = rec.get("latency_s")
    if total is None:
        total = max(0.0, rec.get("done_at", 0.0) - rec.get("born_at", 0.0))
    agg: dict[tuple[str, str], float] = {}
    covered = 0.0
    for sp in rec["spans"]:
        dur = max(0.0, sp["t1"] - sp["t0"])
        agg[(sp["stage"], sp["kind"])] = agg.get(
            (sp["stage"], sp["kind"]), 0.0) + dur
        if sp["kind"] in TOP_LEVEL_KINDS:
            covered += dur
    segments = [{"stage": s, "kind": k, "dur_s": d,
                 "frac": d / total if total > 0 else 0.0}
                for (s, k), d in agg.items()]
    segments.sort(key=lambda seg: -seg["dur_s"])
    return {"total_s": total, "segments": segments,
            "unattributed_s": max(0.0, total - covered)}


def shard_profile(rec: dict) -> dict:
    """Per-shard time of one trace from its ``shard_fetch`` child spans:
    ``{shard_id: {"dur_s", "n_fetches", "hosts", "hedged"}}``. The hot
    shard — the fan-out straggler the request's tail hides behind — is
    ``max(profile, key=lambda s: profile[s]["dur_s"])``. Works on live
    records and on ``from_chrome`` reconstructions alike (shard ids
    recover from the span attrs / stage name)."""
    out: dict[int, dict] = {}
    for sp in rec["spans"]:
        if sp["kind"] != "shard_fetch":
            continue
        attrs = sp.get("attrs", {})
        sid = attrs.get("shard")
        if sid is None:
            try:
                sid = int(sp["stage"].rpartition("_")[2])
            except ValueError:
                continue
        sid = int(sid)
        ent = out.setdefault(sid, {"dur_s": 0.0, "n_fetches": 0,
                                   "hosts": set(), "hedged": 0})
        ent["dur_s"] += max(0.0, sp["t1"] - sp["t0"])
        ent["n_fetches"] += 1
        if attrs.get("host") is not None:
            ent["hosts"].add(attrs["host"])
        if attrs.get("hedged"):
            ent["hedged"] += 1
    return out
