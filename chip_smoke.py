#!/usr/bin/env python3
"""Chip smoke: DIN re-rank served at its published widths on one TPU chip.

Drives the primary serving path once, through the entry points a user
calls — ``MultiScenarioService`` → SEDP → ``din-rerank`` on the wall-clock
``AsyncExecutor`` — with DIN at its published widths (embed 18, history
100, attention MLP 80-40, MLP 200-80) and full published vocabularies,
weights random from a seed. Phases, in order; any failure raises and the
script exits nonzero:

  (a) the default JAX device must be a TPU;
  (b) build the service: print the served widths, the host cube load time
      and the device's peak memory;
  (c) serve 64 requests of 64 candidates; every response must be ok (no
      error, not timed out, degradation tier 0);
  (d) compare 8 served answers with a float32 reference on the same
      params at ``highest`` matmul precision: the pointwise score against
      ``din.serve_scores``, the top-k list against
      ``din.score_candidates(path="jnp")``;
  (e) the compiled rerank program of a served bucket holds the Pallas
      kernel (``tpu_custom_call``), not the interpreter or the XLA impl;
  (f) print the compile count per jitted entry point and compile seconds.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.

    python chip_smoke.py        # on a machine with one TPU chip

The script puts the repo's ``src`` on ``sys.path`` itself. It keeps JAX's
persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR`` says,
else in ``<repo>/.jax_cache`` (``repro.runtime.enable_compile_cache``).
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
# libtpu writes compiler logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SCENARIO = "din-rerank"
HBM_BYTES = 16 * 2**30          # one v5e chip
N_REQUESTS = 64
N_CHECK = 8

# Max |served - reference| on probability-scale scores, by platform.
# cpu: both sides are float32 XLA:CPU; the served top-k goes through the
#   fused decomposition (kernels/rerank_score, impl="xla"), which sums in
#   another order than the broadcast oracle — rerank_bench gates that at
#   1e-5 on logits, and sigmoid's slope is at most 1/4.
# tpu: XLA:TPU runs float32 dots at DEFAULT precision as one bf16 pass
#   (each operand rounded to 8 significant bits, relative 2^-9); the
#   served path keeps that precision and the reference runs at "highest".
#   The random-init DIN's logits are O(1e-3) (tables 0.01*N(0,1), Lecun-
#   scaled MLPs), and rounding the params alone to bf16 moves them by
#   ~2e-5 at published widths; rounding the activations too at most
#   doubles that. 1e-4 on probabilities (4e-4 on logits) covers the bf16
#   pass with room. Not 2^-8 "bf16 resolution at magnitude 1": that is
#   wider than the whole spread of the scores (~1e-3), so it would pass a
#   constant answer. check_reference also requires each request's
#   reference scores to spread wider than twice the bound.
SCORE_BOUND = {"cpu": 1e-5, "tpu": 1e-4}


class PhaseFailed(RuntimeError):
    """A smoke phase found the served path wrong."""


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events (a cache hit still emits a backend-compile event:
    its duration is the retrieval)."""

    def __init__(self):
        self.compile_s = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> "CompileStats":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration_secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def peak_bytes() -> tuple:
    """(peak_bytes_in_use, bytes_limit) of the default device, or
    (None, None) where the backend reports no memory stats."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("bytes_limit")


# ------------------------------------------------------------------ phases

def check_device():
    """(a) The default device is a TPU; anything else ends the run."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's default "
                         f"device is {dev.platform!r}")
    log(f"[a] device: {dev.platform} {dev.device_kind} "
        f"x{len(jax.devices())}")
    return dev


def build_service(reduced: bool = False):
    """(b) ``MultiScenarioService`` with ``din-rerank`` at published widths
    (``reduced=False``) or CPU-sized ones."""
    from repro.configs import registry
    from repro.core.service import MultiScenarioService
    from repro.serve.scenario import get_scenario
    spec = dataclasses.replace(get_scenario(SCENARIO), reduced=reduced)
    t0 = time.perf_counter()
    svc = MultiScenarioService([spec])
    build_s = time.perf_counter() - t0
    rt = svc.runtimes[SCENARIO]
    jax.block_until_ready(rt.buffer.active.payload)
    arch = registry.get(spec.arch_id)
    want = arch.reduced(arch.config) if reduced else arch.config
    mc = rt.model_cfg
    if mc != want:
        raise PhaseFailed(f"served config {mc} is not {want}")
    vocab = " ".join(f"{f.name}={f.vocab}"
                     for f in mc.user_fields + mc.item_fields)
    log(f"[b] served widths: embed_dim={mc.embed_dim} "
        f"seq_len={mc.seq_len} attn_mlp={mc.attn_mlp} mlp={mc.mlp}; "
        f"vocab {vocab}")
    # the cube folds its routing index at the first pin: count it as load
    t0 = time.perf_counter()
    with svc.cube.pin():
        pass
    fold_s = time.perf_counter() - t0
    sub = svc.substrate
    log(f"[b] host cube load: {sub.table_load_s:.3f} s tables + "
        f"{fold_s:.3f} s index fold "
        f"({sum(v for _, v in sub.groups)} rows); service build "
        f"{build_s:.3f} s")
    peak, limit = peak_bytes()
    if peak is None:
        log("[b] peak_bytes_in_use: not reported by this backend")
    else:
        log(f"[b] peak_bytes_in_use: {peak} ({peak / 2**30:.3f} GiB; "
            f"bytes_limit {limit})")
        if peak >= HBM_BYTES:
            raise PhaseFailed(f"peak device memory {peak} B >= 16 GiB")
    return svc


def serve_requests(svc, n: int = N_REQUESTS):
    """(c) Serve ``n`` requests (64 candidates each) on AsyncExecutor;
    every response must be ok."""
    t0 = time.perf_counter()
    rep = svc.run(n_requests=n, executor="async")
    wall_s = time.perf_counter() - t0
    if rep.completed != n:
        raise PhaseFailed(f"{rep.completed} of {n} requests completed")
    bad = []
    n_topk = 0
    cands = []
    for ev in rep.results:
        r = ev.meta["response"]
        ok = (ev.meta.get("error") is None and not r.timed_out
              and r.degraded_tier == 0 and r.score is not None
              and math.isfinite(r.score))
        if not r.from_cache:
            ok = ok and bool(r.topk)
            n_topk += 1
            cands.append(len(ev.payload["candidates"]))
        if not ok:
            bad.append((ev.req_id, ev.meta.get("error"), r))
    if bad:
        raise PhaseFailed(f"{len(bad)} bad responses, first: {bad[0]}")
    log(f"[c] served {n} requests in {wall_s:.3f} s wall (compiles "
        f"included): 0 errors, 0 timed out, 0 degraded; {n_topk} "
        f"re-ranked, candidates after shedding min {min(cands)} "
        f"max {max(cands)}")
    return rep


def _request_inputs(cfg, payload):
    """The request's user and candidate inputs as the reference takes
    them: full uncompacted history, zero-filled candidate side fields
    (recall hands over ids only)."""
    user = {"fields": {f.name: jnp.asarray(
                np.asarray(payload["user_fields"][f.name]))[None]
                for f in cfg.user_fields},
            "hist": jnp.asarray(payload["hist"])[None]}
    item = {f.name: jnp.asarray(np.asarray(payload["item_fields"][f.name]))
            [None] for f in cfg.item_fields}
    ids = np.asarray([c[0] for c in payload["candidates"]], np.int32)
    cand = {"item_id": jnp.asarray(ids)}
    for f in cfg.item_fields:
        if f.name != "item_id":
            shape = (len(ids),) if f.bag == 1 else (len(ids), f.bag)
            cand[f.name] = jnp.zeros(shape, jnp.int32)
    return user, item, ids, cand


def _reference(cfg, params, events, precision):
    """Pointwise scores and per-request {item: score} of the plain float32
    DIN math at one matmul precision."""
    from repro.models.recsys import din
    with jax.default_matmul_precision(precision):
        pointwise = jax.jit(lambda p, b: din.serve_scores(p, b, cfg))
        rank = jax.jit(lambda p, u, c: din.score_candidates(
            p, u, c, cfg, top_k=c["item_id"].shape[0], path="jnp"))
        points, ranked = [], []
        for ev in events:
            user, item, ids, cand = _request_inputs(cfg, ev.payload)
            points.append(float(pointwise(
                params, {"user": user, "item": item})[0]))
            v, i = rank(params, user, cand)
            probs = 1.0 / (1.0 + np.exp(-np.asarray(v, np.float64)))
            ranked.append(dict(zip(ids[np.asarray(i)].tolist(),
                                   probs.tolist())))
    return points, ranked


def check_reference(svc, rep, n_check: int = N_CHECK) -> dict:
    """(d) Served answers against the float32 reference at "highest"."""
    from repro import kernels
    rt = svc.runtimes[SCENARIO]
    cfg = rt.model_cfg
    params = rt.buffer.active.payload
    events = [ev for ev in rep.results if ev.payload.get("topk")][:n_check]
    if len(events) < n_check:
        raise PhaseFailed(f"only {len(events)} re-ranked responses")
    bound = SCORE_BOUND[kernels.platform()]
    hi_pts, hi_rank = _reference(cfg, params, events, "highest")
    df_pts, df_rank = _reference(cfg, params, events, "default")
    d_point = max(abs(ev.payload["score"] - r)
                  for ev, r in zip(events, hi_pts))
    d_topk = 0.0
    d_topk_default = 0.0
    spread = math.inf
    for ev, ref, ref_df in zip(events, hi_rank, df_rank):
        # a bound wider than the scores' own spread would pass a constant
        spread = min(spread, max(ref.values()) - min(ref.values()))
        if spread <= 2 * bound:
            raise PhaseFailed(f"req {ev.req_id}: reference scores spread "
                              f"only {spread}, within 2x the bound {bound}")
        topk = ev.payload["topk"]
        want = min(rt.spec.keep, len(ev.payload["candidates"]))
        items = [item for item, _ in topk]
        if len(topk) != want or len(set(items)) != want:
            raise PhaseFailed(f"req {ev.req_id}: top-k {items} is not "
                              f"{want} distinct candidates")
        if any(item not in ref for item in items):
            raise PhaseFailed(f"req {ev.req_id}: top-k {items} holds "
                              f"non-candidates")
        d_topk = max(d_topk, max(abs(s - ref[item]) for item, s in topk))
        d_topk_default = max(d_topk_default,
                             max(abs(s - ref_df[item]) for item, s in topk))
        # ranking: every served item is in the reference's top `want`,
        # up to ties within the score bound on either side
        kth = sorted(ref.values(), reverse=True)[want - 1]
        if any(ref[item] < kth - 2 * bound for item in items):
            raise PhaseFailed(f"req {ev.req_id}: top-k {items} is not the "
                              f"reference top-{want}")
    d_point_default = max(abs(ev.payload["score"] - r)
                          for ev, r in zip(events, df_pts))
    d_ref = max(abs(a - b) for a, b in zip(hi_pts, df_pts))
    log(f"[d] {len(events)} requests vs float32 reference at 'highest': "
        f"pointwise max|diff| {d_point!r} (bound {bound!r}); top-k "
        f"max|diff| {d_topk!r} (bound {bound!r}); reference scores "
        f"spread >= {spread!r} per request")
    log(f"[d] same at 'default' matmul precision: pointwise "
        f"{d_point_default!r}, top-k {d_topk_default!r}; pointwise "
        f"reference default vs highest {d_ref!r}")
    if not (d_point <= bound and d_topk <= bound):
        raise PhaseFailed(f"served answers off the reference: pointwise "
                          f"{d_point}, top-k {d_topk}, bound {bound}")
    return {"pointwise": d_point, "topk": d_topk, "bound": bound}


def check_kernel(svc, rep) -> None:
    """(e) The compiled rerank program of one served (C, T) bucket calls
    the Pallas kernel."""
    rt = svc.runtimes[SCENARIO]
    cfg = rt.model_cfg
    ev = next(ev for ev in rep.results if ev.payload.get("topk"))
    p = ev.payload
    Cp = rt.cand_buckets.fit(len(p["candidates"]))
    Tb = rt.hist_buckets.fit(max(1, int((np.asarray(p["hist"]) >= 0).sum())))
    i32 = jnp.int32
    user = {"fields": {f.name: jax.ShapeDtypeStruct(
                (1,) + np.shape(p["user_fields"][f.name]), i32)
                for f in cfg.user_fields},
            "hist": jax.ShapeDtypeStruct((1, Tb), i32)}
    cand = {f.name: jax.ShapeDtypeStruct(
                (Cp,) if f.bag == 1 else (Cp, f.bag), i32)
            for f in cfg.item_fields}
    text = rt.rerank.lower(rt.buffer.active.payload, user,
                           cand).compile().as_text()
    n = text.count("tpu_custom_call")
    log(f"[e] rerank program at C={Cp} T={Tb}: {n} tpu_custom_call")
    if n == 0:
        raise PhaseFailed("served rerank program has no tpu_custom_call: "
                          "the Pallas kernel did not compile in")


def report_compiles(svc, stats: CompileStats) -> None:
    """(f) Programs compiled per entry point, bounded by the buckets."""
    rt = svc.runtimes[SCENARIO]
    n_serve, n_rerank = rt.serve.n_traces, rt.rerank.n_traces
    cap_serve = len(rt.batch_buckets.sizes)
    cap_rerank = len(rt.cand_buckets.sizes) * len(rt.hist_buckets.sizes)
    log(f"[f] compiled programs: serve_scores {n_serve} (<= {cap_serve} "
        f"buckets), score_candidates {n_rerank} (<= {cap_rerank}); "
        f"backend compile {stats.compile_s:.3f} s over {stats.programs} "
        f"programs, persistent cache {stats.cache_hits} hits "
        f"{stats.cache_misses} misses")
    if n_serve > cap_serve or n_rerank > cap_rerank:
        raise PhaseFailed("more compiled programs than shape buckets")


def main() -> None:
    dev = check_device()
    from repro.runtime import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    stats = CompileStats().install()
    svc = build_service(reduced=False)
    try:
        rep = serve_requests(svc)
        check_reference(svc, rep)
        check_kernel(svc, rep)
        report_compiles(svc, stats)
        peak, _ = peak_bytes()
        log(f"peak_bytes_in_use after serving: {peak} "
            f"({peak / 2**30:.3f} GiB)")
        if peak >= HBM_BYTES:
            raise PhaseFailed(f"peak device memory {peak} B >= 16 GiB")
    finally:
        # the host cube's disk-tier blocks are memmapped temp files
        shutil.rmtree(svc.cube.tmpdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
