"""Serving launcher: LM decode service with continuous batching + hot-load,
or the recsys JiZHI service (examples/quickstart path), from one CLI.

  PYTHONPATH=src python -m repro.launch.serve --mode recsys --requests 96
  PYTHONPATH=src python -m repro.launch.serve --mode recsys --no-reduced \
      --arch din          # published widths at full vocab: one 16 GiB chip
  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch smollm-135m \
      --requests 6 --reduced

Telemetry (recsys mode): ``--metrics-port`` serves the registry live at
``/metrics`` (Prometheus text exposition) and ``/metrics.json``;
``--metrics-out DIR`` writes both files at shutdown; ``--history-dir``
runs a ``StatsRecorder`` sampling the registry into the windowed history
log the IRM's offline auto-search reads; ``--trace-out FILE`` exports the
run's tail-sampled traces as Chrome trace-event JSON (Perfetto-viewable).
"""
import argparse
import os
import threading
import time

import numpy as np


def start_metrics_server(registry, port: int):
    """Serve /metrics (Prometheus) + /metrics.json from a daemon thread.
    Returns the http.server instance (``.shutdown()`` to stop). Stdlib
    only — no new dependencies."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — http.server API
            if self.path.startswith("/metrics.json"):
                body = registry.to_json().encode()
                ctype = "application/json"
            elif self.path.startswith("/metrics"):
                body = registry.to_prometheus().encode()
                ctype = "text/plain; version=0.0.4"
            else:
                self.send_response(404)
                self.end_headers()
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):       # quiet: metrics scrapes are noise
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True,
                     name="metrics-http").start()
    return srv


def write_metrics_files(registry, out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "metrics.prom"), "w") as f:
        f.write(registry.to_prometheus())
    with open(os.path.join(out_dir, "metrics.json"), "w") as f:
        f.write(registry.to_json())


def serve_recsys(args):
    from repro import obs
    from repro.core.service import InferenceService, ServiceConfig
    cfg = ServiceConfig(
        arch_id=args.arch if args.arch != "smollm-135m" else "din",
        reduced=args.reduced,
        # crash safety (DESIGN.md §9): --snapshot-dir enables periodic
        # durable snapshots + SIGTERM final-snapshot; --recover boots from
        # the newest valid snapshot and replays the delta log
        snapshot_dir=args.snapshot_dir, recover=args.recover,
        live_updates=bool(args.update_dir), update_dir=args.update_dir)
    svc = InferenceService(cfg)
    registry = obs.get_registry()
    obs.bridge.register_service(svc, name="recsys", registry=registry)
    if svc.snapshotter is not None:
        obs.bridge.register_snapshotter(svc.snapshotter, registry=registry)
    metrics_srv = (start_metrics_server(registry, args.metrics_port)
                   if args.metrics_port else None)
    recorder = None
    if args.history_dir:
        recorder = obs.StatsRecorder(
            args.history_dir, registry,
            interval_s=args.history_interval_s).start()
    tracer = obs.Tracer() if args.trace_out else None
    if svc.snapshotter is not None:
        svc.install_shutdown_hook()
    if svc.update_watcher is not None:
        svc.start_updates()
    if args.recover and svc.substrate.recovering:
        print(f"recovering: serving degraded until delta replay reaches "
              f"v{svc.substrate.recovery_target}")
    rep = svc.run(n_requests=args.requests, tracer=tracer)
    registry.histogram("request_latency_s",
                       "end-to-end request latency").observe_many(
        rep.latencies)
    print(f"served {len(rep.results)} requests; "
          f"avg {rep.avg_latency*1e3:.2f} ms, p99 "
          f"{rep.latency_percentile(0.99)*1e3:.2f} ms; "
          f"query-cache hit {100*svc.query_cache.stats.hit_ratio:.1f}%")
    if recorder is not None:
        recorder.stop()
        print(f"history: {recorder.windows_published} window(s) in "
              f"{args.history_dir}")
    if tracer is not None:
        tracer.buffer.export_chrome(args.trace_out)
        print(f"traces: {len(tracer.buffer.traces())} retained "
              f"-> {args.trace_out}")
    if args.metrics_out:
        write_metrics_files(registry, args.metrics_out)
        print(f"metrics: {args.metrics_out}/metrics.prom + metrics.json")
    if metrics_srv is not None:
        metrics_srv.shutdown()
    if svc.snapshotter is not None:
        path = svc.shutdown()
        if path:
            print(f"final snapshot: {path}")


def serve_lm(args):
    import jax
    import jax.numpy as jnp
    from repro.configs import registry
    from repro.models import transformer
    from repro.serve.batcher import ContinuousBatcher
    from repro.serve.hotload import DoubleBuffer, Generation

    arch = registry.get(args.arch)
    cfg = arch.reduced(arch.config) if args.reduced else arch.config
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    buf = DoubleBuffer(Generation(0, params))
    n_slots, s_max = 4, 64
    batcher = ContinuousBatcher(n_slots, s_max)

    rng = np.random.default_rng(0)
    prompts = {i: rng.integers(0, cfg.vocab, (8,), dtype=np.int32)
               for i in range(args.requests)}
    for i, p in prompts.items():
        batcher.submit(i, len(p), max_new=8)

    # one shared cache table for the slot batch
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        transformer.KVCache.shapes(cfg, n_slots, s_max))
    cache = cache._replace(length=jnp.asarray(0, jnp.int32))
    # prefill each admitted slot (batch-1 prefill per join keeps it simple)
    toks = jnp.stack([jnp.asarray(prompts[s.request_id])
                      for s in batcher.slots if s.request_id is not None])
    logits, cache = transformer.prefill(buf.active.payload, toks, cfg,
                                        smax=s_max)
    decode = jax.jit(lambda p, c, t: transformer.decode_step(p, c, t, cfg))
    last = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    t0 = time.monotonic()
    steps = 0
    while batcher.active_mask.any() and steps < 32:
        logits, cache = decode(buf.active.payload, cache, last)
        last = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        eos = np.asarray(last[:, 0] % 97 == 0)       # toy EOS criterion
        batcher.step_complete(eos)
        steps += 1
    print(f"decoded {steps} steps for {args.requests} requests "
          f"({(time.monotonic()-t0)/max(1,steps)*1e3:.1f} ms/step, "
          f"slot utilization {batcher.utilization:.2f}, "
          f"completed {len(batcher.completed)})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["recsys", "lm"], default="recsys")
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the CPU-sized config; --no-reduced serves "
                         "the published widths at full vocab")
    ap.add_argument("--snapshot-dir", default=None,
                    help="recsys: durable cube snapshots here (enables "
                         "periodic snapshot + SIGTERM final snapshot)")
    ap.add_argument("--recover", action="store_true",
                    help="recsys: boot from the newest valid snapshot and "
                         "replay the delta log (cold boot if none)")
    ap.add_argument("--update-dir", default=None,
                    help="recsys: tail this delta log (live updates)")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="recsys: serve /metrics (Prometheus) + "
                         "/metrics.json on this localhost port")
    ap.add_argument("--metrics-out", default=None,
                    help="recsys: write metrics.prom + metrics.json into "
                         "this directory at shutdown")
    ap.add_argument("--history-dir", default=None,
                    help="recsys: record windowed registry history here "
                         "(the IRM offline auto-search input)")
    ap.add_argument("--history-interval-s", type=float, default=1.0)
    ap.add_argument("--trace-out", default=None,
                    help="recsys: export tail-sampled request traces as "
                         "Chrome trace-event JSON to this file")
    args = ap.parse_args()
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    if args.mode == "recsys":
        serve_recsys(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
