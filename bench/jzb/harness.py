"""One cell of the benchmark: build the served path, install the seed's
weights, warm every shape the traffic reaches, drive an open-loop window,
read the metrics and decide ``correct``.

The system under test is the program's own entry: ``MultiScenarioService``
with the configuration's scenario at published widths, its SEDP plan on
the wall-clock ``AsyncExecutor`` (ingress → fanout → query cache →
features → cube → shed → rerank → respond). The harness changes none of
it. It paces the requests itself: ``AsyncExecutor.run`` takes a list, and
the list handed to it releases each request at its due time, so every
latency runs from the due time, and the generator's lateness is recorded.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import re
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

from jzb import traffic as tgen
from jzb.config import check_served, model_cfg
from jzb.manifest import BENCH, Manifest, load

TRACE_LEAD_S = 1.0      # the profiler starts this long into the window
TRACE_S = 3.0           # and records this long
N_SAMPLE = 48           # answered requests compared with the reference
PREWINDOW_S = 3.0       # the cell's traffic paced through at set-up's end


def log(msg: str) -> None:
    print(msg, flush=True)


def percentile(xs, q: float) -> float:
    """Nearest-rank percentile: the smallest x with ceil(q n) samples <= x."""
    xs = np.sort(np.asarray(xs, np.float64))
    return float(xs[max(0, math.ceil(q * len(xs)) - 1)])


class CompileCounter:
    """Backend compiles, from JAX's monitoring events (a persistent-cache
    hit counts too: it still loads a program)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Paced(list):
    """The events, released one by one at their due times (monotonic
    seconds) when the executor iterates them."""

    def __init__(self, events, due):
        super().__init__(events)
        self.due = due
        self.release = np.zeros(len(events))

    def __iter__(self):
        for i, ev in enumerate(list.__iter__(self)):
            wait = self.due[i] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            self.release[i] = time.monotonic()
            yield ev


@dataclasses.dataclass
class Window:
    """What one measured window produced, for the metric readers."""
    cell: "Cell"
    events: list
    due: np.ndarray            # monotonic due times
    release: np.ndarray        # monotonic release times
    t_end: float
    seconds: float
    report: object
    answered: list             # indices of answered requests
    ok: np.ndarray             # answered ok within the deadline
    latency_s: np.ndarray
    trace: dict | None = None          # devtrace.reduce output
    trace_mono: tuple | None = None    # the traced window, monotonic

    def stage(self, short: str):
        """The executor's ``StageStats`` of this scenario's stage."""
        return self.report.stage_stats.get(f"{self.cell.scenario}.{short}")

    def spans(self, i: int, short: str) -> list:
        """Request i's tracer spans at this scenario's stage."""
        name = f"{self.cell.scenario}.{short}"
        return [s for s in self.events[i].meta.get("spans", ())
                if s["stage"] == name]

    def reranked(self) -> list:
        """Answered requests that the model stage scored."""
        return [i for i in self.answered
                if "generation" in self.events[i].payload]

    def in_trace(self, i: int) -> bool:
        """Whether request i left the model stage inside the traced
        window."""
        ex = [s for s in self.spans(i, "rerank") if s["kind"] == "exec"]
        return bool(ex) and self.trace_mono is not None and \
            self.trace_mono[0] <= ex[-1]["t1"] <= self.trace_mono[1]

    def work(self, i: int) -> tuple:
        """(valid history rows, candidates scored) of request i."""
        p = self.events[i].payload
        return (int((np.asarray(p.hist) >= 0).sum()),
                len(p["candidates"]) if p.get("topk") else 0)


class Cell:
    """A workload of ``BENCHMARK.json`` and the service it runs on.

    ``reduced=True`` serves the program's CPU-sized variant of the
    configuration (tests only); ``allow_cpu`` skips the look for a chip."""

    def __init__(self, name: str, manifest: Manifest | None = None,
                 reduced: bool = False, allow_cpu: bool = False,
                 cache: bool = True):
        self.manifest = manifest or Manifest()
        self.spec = self.manifest.cell(name)
        self.name = name
        self.cfg = self.manifest.config(self.spec["config"])
        self.traffic = self.manifest.traffic(self.spec["traffic"])
        self.limits = json.loads(
            (BENCH / "limits" / f"{self.spec['config']}.json").read_text())
        self.reduced = reduced
        if self.cfg.get("vocab_share", 1) != 1:
            raise SystemExit("the program cannot serve a share of the "
                             "vocabulary yet: vocab_share must be 1")
        self.device = self._device(self.spec["chips"], allow_cpu)
        import jax
        if cache:
            jax.config.update("jax_compilation_cache_dir",
                              str(BENCH / ".cache" / "jax"))
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
        if self.cfg["matmul_precision"] != "default":
            jax.config.update("jax_default_matmul_precision",
                              self.cfg["matmul_precision"])
        self.compiles = CompileCounter()
        self.model = load("models", self.cfg["model"])
        self.parts: dict = {}
        self.params = None

    @staticmethod
    def _device(chips: int, allow_cpu: bool):
        import jax
        devs = jax.devices()
        if not allow_cpu and (devs[0].platform != "tpu" or len(devs) < chips):
            raise SystemExit(f"needs {chips} TPU chip(s); JAX found "
                             f"{len(devs)} {devs[0].platform} device(s)")
        return devs[0]

    # ---------------------------------------------------------- set-up
    def build(self) -> None:
        """The program's service for this configuration, with the cube's
        routing index folded (the program folds it at the first pin)."""
        from repro.core.service import MultiScenarioService
        from repro.serve.scenario import get_scenario
        t = time.monotonic()
        spec = dataclasses.replace(
            get_scenario(self.cfg["scenario"]), reduced=self.reduced,
            seed=0, batch_size=self.cfg["batch_size"], keep=self.cfg["keep"])
        self.svc = MultiScenarioService([spec])
        self.rt = self.svc.runtimes[spec.name]
        self.scenario = spec.name
        self.parts["build_s"] = time.monotonic() - t
        self.parts["cube_tables_s"] = self.svc.substrate.table_load_s
        t = time.monotonic()
        with self.svc.cube.pin():
            pass
        self.parts["cube_fold_s"] = time.monotonic() - t
        if self.reduced:
            self.cfg = _reduced_cfg(self.cfg, self.rt.model_cfg)
        self.mc = model_cfg(self.cfg)
        check_served(self.mc, self.rt.model_cfg)

    def install_weights(self, seed: int) -> None:
        """The seed's weights, made on the device in one jitted call, put
        in the program's place through its hot-load buffer. The program's
        own init is dropped first, so the chip never holds both."""
        import jax
        from repro.serve.hotload import Generation
        t = time.monotonic()
        self.rt.buffer.active.payload = None
        self.params = None
        gc.collect()
        init = jax.jit(self.model.init, static_argnums=1)
        self.params = jax.block_until_ready(init(prng_key(seed), self.mc))
        self.rt.buffer.load(Generation(self.rt.buffer.active.stamp + 1,
                                       self.params))
        self.parts["weights_s"] = time.monotonic() - t

    def make_events(self, seed: int, seconds: float):
        """The window's requests (program ``Event``s) and their due times
        in seconds from the window's start."""
        t = time.monotonic()
        tr = tgen.make_traffic(self.cfg, self.traffic, seed, seconds)
        events = to_events(self.cfg, self.traffic, tr)
        self.tr = tr
        self.parts["traffic_s"] = time.monotonic() - t
        return tr, events

    def warm(self, tr) -> None:
        """Compile every shape the window can reach. A short burst of
        requests goes through the executor first, so that the pointwise
        batches are packed from payloads as the stages leave them; then
        each pointwise batch bucket, each (candidates, history) bucket of
        the re-rank and the shedder's DNN are called once. Last,
        ``PREWINDOW_S`` of the cell's traffic (from a fixed seed, the same
        for every run) is paced through the service as the window paces
        it."""
        import jax
        from repro.core.executors import AsyncExecutor
        t = time.monotonic()
        rt = self.rt
        params = self.params
        burst = to_events(self.cfg, self.traffic, tgen.make_traffic(
            self.cfg, self.traffic, 0xB0057, 0.25), limit=64)
        AsyncExecutor(self.svc.plan).run(burst)
        self.parts["burst_s"] = time.monotonic() - t
        t = time.monotonic()
        packed = next(ev.payload for ev in burst
                      if "cube_rows" in ev.payload)
        for b in rt.batch_buckets.sizes:
            jax.block_until_ready(rt.serve(params,
                                           rt.pack_batch([packed] * b)))
        cd, hd = self.traffic["candidates"], self.traffic["history"]
        lo = min(rt.shedder.min_keep if rt.shedder else cd["min"], cd["min"])
        cs = sorted({rt.cand_buckets.fit(c) for c in range(lo, cd["max"] + 1)})
        ts = sorted({rt.hist_buckets.fit(n)
                     for n in range(hd["min"], min(hd["max"],
                                                   self.mc.seq_len) + 1)})
        for T in ts:
            for C in cs:
                p = packed.copy()
                p.hist = np.full(self.mc.seq_len, -1, np.int32)
                p.hist[:T] = 0
                p.candidates = [(j, 0.5) for j in range(C)]
                rt.rerank_candidates(params, p, keep=self.cfg["keep"])
        if rt.shedder is not None:
            rt.shedder.dnn(np.zeros((1, 7), np.float32))
        self.parts["compile_s"] = time.monotonic() - t
        t = time.monotonic()
        # the window starts from a served state: the shedder's quota
        # controller and the query cache settled by paced traffic
        pre = tgen.make_traffic(self.cfg, self.traffic, 0x9E5EED, PREWINDOW_S)
        t0 = time.monotonic() + 0.05
        AsyncExecutor(self.svc.plan).run(
            Paced(to_events(self.cfg, self.traffic, pre), t0 + pre.due_s))
        self.parts["prewindow_s"] = time.monotonic() - t
        t = time.monotonic()
        gc.collect()        # set-up's garbage is not the window's to sweep
        self.parts["gc_s"] = time.monotonic() - t

    # ---------------------------------------------------------- window
    def window(self, tr, events, seconds: float, trace: bool) -> Window:
        from repro.core.executors import AsyncExecutor
        from repro.obs.trace import Tracer
        from repro.serve.stages import Response
        from repro.core.irm.shedding import ShedderState
        if self.rt.shedder is not None:
            self.rt.shedder.state = ShedderState()
        restore = self._wrap_ops(trace)
        ex = AsyncExecutor(self.svc.plan, tracer=Tracer() if trace else None)
        n_compiles = self.compiles.n
        t0 = time.monotonic() + 0.05
        due = t0 + tr.due_s
        paced = Paced(events, due)
        prof = _Profiler(t0 + TRACE_LEAD_S, TRACE_S) if trace else None
        pauses = _GcPauses()
        try:
            report = ex.run(paced)
        finally:
            pauses.stop()
        self.gc_pauses = pauses.seconds
        t_end = time.monotonic()
        restore()
        if prof is not None:
            prof.join()
        self.window_compiles = self.compiles.n - n_compiles
        self.shedder_state = self.rt.shedder.state if self.rt.shedder \
            else None
        deadline = self.traffic["deadline_ms"] / 1e3
        lat = np.empty(len(events))
        ok = np.zeros(len(events), bool)
        answered = []
        for i, ev in enumerate(events):
            if "response" not in ev.meta:
                ev.meta["response"] = Response.from_event(ev)
            r = ev.meta["response"]
            failed = bool(ev.meta.get("error")) or not ev.done_at
            lat[i] = (t_end if failed else ev.done_at) - due[i]
            if failed or r.timed_out:
                continue
            answered.append(i)
            ok[i] = (r.degraded_tier == 0 and r.score is not None
                     and math.isfinite(r.score)
                     and (r.from_cache or bool(r.topk))
                     and lat[i] <= deadline)
        w = Window(cell=self, events=events, due=due, release=paced.release,
                   t_end=t_end, seconds=seconds, report=report,
                   answered=answered, ok=ok, latency_s=lat)
        if prof is not None:
            w.trace, w.trace_mono = self._reduce_trace(prof)
        return w

    def _wrap_ops(self, trace: bool):
        """Wrap every stage op to keep its longest call (host stalls show
        there) and, in a traced run, in a ``TraceAnnotation`` named after
        the stage, carrying its batch size, so the device trace can blame
        idle gaps on the host stage that ran in them. Returns the undo."""
        import jax
        saved = {}
        self.longest_op = {}
        for name, sp in self.svc.plan.stages.items():
            saved[name] = sp.op

            def op(batch, ctx, _op=sp.op, _name=name):
                t = time.perf_counter()
                if trace:
                    with jax.profiler.TraceAnnotation(_name, n=len(batch)):
                        out = _op(batch, ctx)
                else:
                    out = _op(batch, ctx)
                dt = time.perf_counter() - t
                if dt > self.longest_op.get(_name, 0.0):
                    self.longest_op[_name] = dt
                return out
            sp.op = op

        def restore():
            for name, op in saved.items():
                self.svc.plan.stages[name].op = op
        return restore

    def _reduce_trace(self, prof) -> tuple:
        from jzb import devtrace
        names = set(self.svc.plan.stages) | {"bench.window"}
        doc = devtrace.extract(devtrace.find_xplane(prof.dir), names)
        prof.cleanup()
        if not doc["devices"] and self.device.platform != "tpu":
            return None, None       # a CPU test run: no device plane
        win = [h for h in doc["host"] if h[0] == "bench.window"]
        if not win:
            raise RuntimeError("the trace lost its window annotation")
        w = (win[0][1], win[0][1] + win[0][2])
        doc["host"] = [h for h in doc["host"] if h[0] != "bench.window"]
        if os.environ.get("JZB_DUMP_TRACE"):
            Path(os.environ["JZB_DUMP_TRACE"]).write_text(json.dumps(
                dict(devtrace.trim(doc, 3000), window_ns=w)))
        kernels = {k: load("kernels", k).TRACE_NAMES
                   for k in self.cfg.get("kernels", [])}
        red = devtrace.reduce(doc, w, kernels=kernels,
                              modules=tuple(self.cfg["programs"]))
        return red, (prof.t_start, prof.t_stop)

    # ------------------------------------------------------------ check
    def offered(self, i: int) -> dict:
        """Request i's candidates as generated: item id → recall score."""
        ids, scores = self.tr.candidates(i)
        return dict(zip(ids.tolist(), scores.tolist()))

    def free_program(self) -> None:
        """Drop the service (host cube, caches, executor state) and the
        cube's memmapped disk tier; the seed's weights stay for the
        reference."""
        import shutil
        shutil.rmtree(self.svc.cube.tmpdir, ignore_errors=True)
        self.svc = None
        self.rt = None
        gc.collect()

    def peak_bytes(self) -> int:
        stats = self.device.memory_stats() or {}
        return int(stats.get("peak_bytes_in_use", 0))

    def result(self, w: Window, trace: bool, t_process: float,
               t_window: float, peak: int, checks: dict,
               correct: bool) -> dict:
        """The run's last line. ``peak``: the device's peak bytes, read
        when the window closed."""
        import jax
        metrics = {}
        if not trace:
            vals = {"setup_s": t_window - t_process,
                    "goodput_rps": float(w.ok.sum()) / w.seconds}
            for m in self.manifest.end_to_end(self.name):
                q = re.fullmatch(r"p(\d+)_ms", m["name"])
                v = (percentile(w.latency_s * 1e3, int(q.group(1)) / 100)
                     if q else vals[m["name"]])
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in self.manifest.per_layer(self.name):
                v = self.manifest.reader(m["name"])(w)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
        dev = {"platform": self.device.platform,
               "kind": self.device.device_kind,
               "count": len(jax.devices()),
               "memory_peak_bytes": peak}
        out = {"correct": bool(correct), "attempted": len(w.events),
               "failed": int(sum(1 for ev in w.events
                                 if ev.meta.get("error") or not ev.done_at)),
               "metrics": metrics, "device": dev}
        if trace and w.trace is not None:
            dev["busy_s"] = w.trace["busy_s"]
            dev["window_s"] = w.trace["window_s"]
            out["breakdown"] = {"device_ops": w.trace["device_ops"],
                                "idle_gaps": w.trace["idle_gaps"]}
        out["checks"] = checks
        return out

    def peaks(self) -> dict | None:
        """The device kind's peaks; None off a TPU (tests on the CPU)."""
        if self.device.platform != "tpu":
            return None
        kind = self.device.device_kind
        peaks = json.loads((BENCH / "jzb" / "peaks.json").read_text())
        if kind not in peaks:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           f"bench/jzb/peaks.json")
        return peaks[kind]


class _GcPauses:
    """Durations of the interpreter's garbage collections while it is on
    (every stage thread waits for them)."""

    def __init__(self):
        self.seconds: list = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds.append(time.perf_counter() - self._t)

    def stop(self):
        gc.callbacks.remove(self._on)


class _Profiler:
    """Starts ``jax.profiler`` at ``at`` (monotonic) for ``seconds``, on a
    thread of its own, with the traced span marked ``bench.window``."""

    def __init__(self, at: float, seconds: float):
        self.dir = tempfile.mkdtemp(prefix="jzb_trace_")
        self.t_start = self.t_stop = None
        self._th = threading.Thread(target=self._run, args=(at, seconds),
                                    daemon=True)
        self._th.start()

    def _run(self, at, seconds):
        import jax
        time.sleep(max(0.0, at - time.monotonic()))
        # host spans from TraceAnnotation only: the Python tracer would
        # record every call of every stage thread
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            self.t_start = time.monotonic()
            time.sleep(seconds)
            self.t_stop = time.monotonic()
        jax.profiler.stop_trace()

    def join(self):
        self._th.join()

    def cleanup(self):
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)


def _reduced_cfg(cfg: dict, served) -> dict:
    """The configuration with the sizes of the program's CPU-sized
    variant (tests only)."""
    def fields(fs):
        return [dict(name=f.name, vocab=f.vocab, bag=f.bag,
                     combiner=f.combiner) for f in fs]
    return dict(cfg, embed_dim=served.embed_dim, seq_len=served.seq_len,
                user_fields=fields(served.user_fields),
                item_fields=fields(served.item_fields),
                attn_mlp=list(served.attn_mlp), mlp=list(served.mlp),
                gru_dim=served.gru_dim)


def prng_key(seed: int):
    """A threefry key from any non-negative integer seed (two 32-bit words
    of numpy's SeedSequence, so seeds past 2**32 stay distinct)."""
    import jax
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def to_events(cfg: dict, traffic: dict, tr, limit: int | None = None):
    """Program ``Event``s carrying typed ``Request``s, with the cell's
    deadline as their latency budget."""
    from repro.core.sedp import Event
    from repro.serve.stages import Request
    users: dict = {}
    items: dict = {}
    deadline_s = traffic["deadline_ms"] / 1e3
    out = []
    for i in range(len(tr) if limit is None else min(limit, len(tr))):
        u, it = int(tr.user[i]), int(tr.item[i])
        if u not in users:
            users[u] = (_i32(tgen.user_fields(cfg, u)),
                        tgen.user_history(cfg, traffic, u).astype(np.int32))
        if it not in items:
            items[it] = _i32(tgen.item_fields(cfg, it))
        ids, scores = tr.candidates(i)
        req = Request(user_id=u, item_id=it, user_fields=users[u][0],
                      item_fields=items[it], hist=users[u][1],
                      candidates=list(zip(ids.tolist(), scores.tolist())))
        ev = Event(payload=req)
        ev.meta["deadline_s"] = deadline_s
        out.append(ev)
    return out


def _i32(fields: dict) -> dict:
    return {k: (np.int32(v) if np.ndim(v) == 0 else np.asarray(v, np.int32))
            for k, v in fields.items()}


def run(name: str, seed: int, seconds: float, trace: bool,
        t_process: float, fault=None, mix=None, **cell_kw) -> dict:
    """One run of a cell, start to result line. ``fault(cell)``, where
    given, breaks the served path after set-up, and ``mix`` overrides
    traffic parameters (tests of ``correct`` on the CPU)."""
    from jzb import check
    cell = Cell(name, **cell_kw)
    cell.traffic.update(mix or {})
    log(f"device: {cell.device.platform} {cell.device.device_kind}")
    cell.build()
    cell.install_weights(seed)
    tr, events = cell.make_events(seed, seconds)
    cell.warm(tr)
    log("setup parts: " + " ".join(f"{k}={v:.3f}"
                                   for k, v in cell.parts.items()))
    if fault is not None:
        fault(cell)
    t_window = time.monotonic()
    w = cell.window(tr, events, seconds, trace)
    peak = cell.peak_bytes()
    late_ms = (w.release - w.due) * 1e3
    lat_ms = w.latency_s * 1e3
    done = np.sort([ev.done_at for ev in w.events if ev.done_at])
    log(f"window: {len(events)} requests over {seconds} s; "
        f"{len(w.answered)} answered; latency p50 "
        f"{percentile(lat_ms, 0.5):.4f} ms p99 {percentile(lat_ms, 0.99):.4f}"
        f" ms; backend compiles inside the window "
        f"{cell.window_compiles}; generator late p50 "
        f"{percentile(late_ms, 0.5):.4f} ms max {float(late_ms.max()):.4f}"
        f" ms; longest gap between completions "
        f"{float(np.diff(done).max()) * 1e3:.1f} ms; gc {len(cell.gc_pauses)}"
        f" collections, longest {max(cell.gc_pauses, default=0) * 1e3:.1f}"
        f" ms; drain {w.t_end - w.due[-1]:.3f} s")
    log("longest stage op (ms): " + " ".join(
        f"{k}={v * 1e3:.1f}" for k, v in sorted(cell.longest_op.items())))
    params = cell.params
    cell.free_program()
    readings = check.compare(w, params, seed, N_SAMPLE)
    checks, correct = check.judge(readings, cell.limits)
    return cell.result(w, trace, t_process, t_window, peak, checks, correct)
