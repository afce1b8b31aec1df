"""The comparison that decides ``correct``.

What the timed window served is compared with the plain float32 reference
(``bench/models/<model>.py``), which imports nothing of the program and
takes only the weights the benchmark made. Numbers, each with its limit
from ``bench/limits/<config>.json``:

* ``point_gap``: the widest |served - reference| of a pointwise score,
  over a sample of answered requests drawn from the seed (query-cache
  answers included), probability scale;
* ``cand_gap``: the widest |served - reference| of a re-ranked
  candidate's score in the sampled top-k lists;
* ``rank_violations``: sampled top-k lists holding an item whose
  reference score lies below the reference's k-th best of the kept
  candidates by more than twice the ``cand_gap`` limit, more than score
  noise within that limit can swap (exact: limit 0);
* ``shed_violations``: over every answered re-rank, top-k entries that
  are not kept candidates, duplicates, a top-k of the wrong length, kept
  candidates that were never offered, or a kept set that is not the
  offered candidates with the best recall scores (exact: limit 0);
* ``shed_share``: the share of offered candidates (%) that the answered
  re-ranks lost to the shed stage. The shedder adapts its cut to the
  load, so each answer stands on fewer candidates as it sheds more: a
  top-k over fewer candidates is a different answer, not a faster one.
  The limit lies between what sound runs at the cell's load shed and what
  a shedder that keeps only its floor of candidates sheds;
* ``unanswered``: requests that errored or never completed (limit 0).
  A request that timed out was late, not wrong: the latency metrics
  count it.

The control is the same reference at the nearest precision below the
one the configuration states (its ``control_precision``), put in the
served path's place: its pointwise scores and its own top-k over the same
kept candidates, compared in the same way.
"""
from __future__ import annotations

import numpy as np

from jzb.nets import MATMULS

GAP_NUMBERS = ("point_gap", "cand_gap")


def _sigmoid(x) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64)))


def sample(w, seed: int, n: int) -> list:
    """Answered requests drawn from the seed, with the re-ranked request
    of the most (candidates x history rows) among them."""
    rng = np.random.default_rng([int(seed), 0xC4EC])
    pool = list(w.answered)
    pick = list(rng.choice(pool, size=min(n, len(pool)), replace=False))
    rr = w.reranked()
    if rr:
        big = max(rr, key=lambda i: w.work(i)[0] * max(1, w.work(i)[1]))
        if big not in pick:
            pick.append(big)
    return [int(i) for i in pick]


class Reference:
    """The reference logits of one request: its pointwise target first,
    then its kept candidates padded to ``c_max``, in one jitted call."""

    def __init__(self, model, mc, c_max: int, precision: str):
        import jax
        mm = MATMULS[precision]
        self.mc, self.c_max = mc, c_max

        def fn(params, user, hist, tids, side):
            return model.logits(params, mc, user, hist, tids, side, mm)
        self._fn = jax.jit(fn)

    def __call__(self, params, payload, kept_ids) -> tuple:
        import jax.numpy as jnp
        mc = self.mc
        user = {f.name: jnp.asarray(np.asarray(
            payload.user_fields[f.name], np.int32).reshape(f.bag))
            for f in mc.user_fields}
        c = len(kept_ids)
        tids = np.zeros(1 + self.c_max, np.int32)
        tids[0] = payload.item_id
        tids[1:1 + c] = kept_ids
        side = {}
        for f in mc.side_item_fields:
            s = np.zeros((1 + self.c_max, f.bag), np.int32)
            s[0] = np.asarray(payload.item_fields[f.name]).reshape(f.bag)
            side[f.name] = jnp.asarray(s)
        out = np.asarray(self._fn(params, user, jnp.asarray(
            np.asarray(payload.hist, np.int32)), jnp.asarray(tids), side))
        return out[0], out[1:1 + c]


def _gaps(point_served, point_ref, topk, kept_ids, ref_kept) -> tuple:
    """(point gap, candidate gap, rank gap) of one request, probability
    scale; None where the request has no such answer."""
    pg = (abs(point_served - point_ref) if point_served is not None
          else None)
    if not topk:
        return pg, None, None
    ref = dict(zip(kept_ids.tolist(), _sigmoid(ref_kept).tolist()))
    cg = max(abs(p - ref.get(item, np.inf)) for item, p in topk)
    kth = np.sort(list(ref.values()))[::-1][len(topk) - 1]
    rg = max(0.0, max(kth - ref.get(item, -np.inf) for item, _ in topk))
    return pg, cg, rg


def shed_violations(w) -> int:
    """Exact checks of every answered re-rank against the shed rule."""
    bad = 0
    keep = w.cell.cfg["keep"]
    for i in w.reranked():
        p = w.events[i].payload
        topk = p.get("topk") or []
        kept = [c[0] for c in p["candidates"]]
        offered = w.cell.offered(i)
        items = [t[0] for t in topk]
        bad += sum(1 for t in items if t not in set(kept))
        bad += len(items) - len(set(items))
        bad += int(len(items) != min(keep, len(kept)))
        bad += sum(1 for k in kept if k not in offered)
        # the shed stage ranks by float32 recall score: every kept
        # candidate scores at least as high as every one it dropped
        f32 = {k: np.float32(v) for k, v in offered.items()}
        dropped = set(offered) - set(kept)
        if kept and dropped:
            bad += int(min(f32[k] for k in kept if k in f32)
                       < max(f32[k] for k in dropped))
    return bad


def shed_share(w) -> float:
    """% of the offered candidates of every answered re-rank that the
    shed stage dropped."""
    offered = kept = 0
    for i in w.reranked():
        offered += len(w.cell.tr.candidates(i)[0])
        kept += len(w.events[i].payload["candidates"])
    return 100.0 * (offered - kept) / offered if offered else 0.0


def compare(w, params, seed: int, n_sample: int, control: bool = False
            ) -> dict:
    """The readings of one run: the served path's, or the control's."""
    cell = w.cell
    c_max = cell.traffic["candidates"]["max"]
    ref = Reference(cell.model, cell.mc, c_max, "highest")
    low = (Reference(cell.model, cell.mc, c_max,
                     cell.cfg["control_precision"]) if control else None)
    keep = cell.cfg["keep"]
    tie = 2 * cell.limits["cand_gap"]["limit"]
    worst = dict.fromkeys(GAP_NUMBERS, 0.0)
    worst["rank_violations"] = 0
    for i in sample(w, seed, n_sample):
        p = w.events[i].payload
        kept = np.asarray([c[0] for c in p["candidates"]], np.int64)
        l_point, l_kept = ref(params, p, kept)
        if control:
            c_point, c_kept = low(params, p, kept)
            served = float(_sigmoid(c_point))
            order = np.argsort(-c_kept, kind="stable")[:min(keep, len(kept))]
            topk = ([(int(kept[j]), float(_sigmoid(c_kept[j])))
                     for j in order] if p.get("topk") else None)
        else:
            served, topk = p.get("score"), p.get("topk")
        pg, cg, rg = _gaps(served, float(_sigmoid(l_point)), topk, kept,
                           l_kept)
        for k, v in zip(GAP_NUMBERS, (pg, cg)):
            if v is not None:
                worst[k] = max(worst[k], float(v))
        worst["rank_violations"] += int(rg is not None and rg > tie)
    if not control:
        worst["shed_violations"] = shed_violations(w)
        worst["shed_share"] = shed_share(w)
        worst["unanswered"] = sum(1 for ev in w.events
                                  if ev.meta.get("error") or not ev.done_at)
    return worst


def judge(readings: dict, limits: dict) -> tuple:
    """(checks for the result line, correct)."""
    checks = {k: {"value": v, "limit": limits[k]["limit"]}
              for k, v in readings.items()}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())
