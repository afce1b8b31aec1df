"""Open-loop request traffic for the re-rank cells, generated from a seed.

One generator serves every cell; a traffic file (``bench/traffic/<name>.json``)
holds its parameters. What a seed decides and what it does not:

* The sizes are the same multiset for every seed: candidate counts and
  inter-arrival gaps are the quantiles of their distributions at
  ``(i + 0.5) / n``, put in an order drawn from the seed. So two seeds
  offer the same amount of work, in another order and to other users.
* User and request item ids are Zipf over the configuration's
  vocabularies, drawn from the seed. Candidates are distinct item ids,
  Zipf too, with uniform recall scores.
* Everything about a user is a function of the user id alone (its
  history, its length, its profile bag), and an item's side fields of the
  item id alone, as in a deployment: so a query-cache answer for a
  (user, item) pair is the score of this request's own inputs.

``zipf_ids`` and ``diurnal_burst_arrivals`` are copies of the program's
``repro.data.synthetic`` functions; the request builder replaces its
``make_request_events``, whose candidates were always items ``0..63`` and
whose history lengths were uniform.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

_SALT = 0x9E3779B97F4A7C15


def zipf_ids(rng: np.random.Generator, n: int, vocab: int,
             a: float = 1.05) -> np.ndarray:
    """Zipf over [0, vocab): rank r maps to id r - 1 (mod vocab)."""
    z = rng.zipf(a, size=n).astype(np.int64)
    return ((z - 1) % vocab).astype(np.int64)


def _arrival_streams(rng: np.random.Generator):
    seeds = rng.integers(0, np.iinfo(np.int64).max, size=3)
    return tuple(np.random.default_rng(int(s)) for s in seeds)


def diurnal_burst_arrivals(rng: np.random.Generator, n_events: int,
                           base_qps: float, peak_mult: float = 3.0,
                           day_s: float = 86400.0, start_frac: float = 0.5,
                           burst_rate_per_s: float = 0.0,
                           burst_mult: float = 3.0,
                           burst_dur_s: float = 0.5) -> np.ndarray:
    """Non-homogeneous Poisson arrivals by Lewis thinning: a cosine day
    curve between ``base_qps`` and ``base_qps * peak_mult``, times Poisson
    burst windows of ``burst_mult`` for ``burst_dur_s``. Sorted times in
    seconds from 0."""
    arr_rng, burst_rng, acc_rng = _arrival_streams(rng)
    lam_max = base_qps * max(1.0, peak_mult) * (
        max(1.0, burst_mult) if burst_rate_per_s > 0 else 1.0)
    mean_accept = max(1e-3, 0.5 * (1.0 + peak_mult) * base_qps / lam_max)
    out: list = []
    got = 0
    t0 = 0.0
    b_starts = np.empty(0)
    b_cursor = 0.0
    while got < n_events:
        need = n_events - got
        chunk = max(1024, int(need / mean_accept * 1.1) + 16)
        gaps = arr_rng.exponential(1.0 / lam_max, size=chunk)
        ts = np.cumsum(np.concatenate(([t0], gaps)))[1:]
        t0 = float(ts[-1])
        phase = np.cos((start_frac + ts / day_s) * 2.0 * np.pi)
        lam = base_qps * (1.0 + (peak_mult - 1.0) * 0.5 * (1.0 + phase))
        if burst_rate_per_s > 0:
            while b_cursor <= t0:
                g = burst_rng.exponential(1.0 / burst_rate_per_s,
                                          size=max(chunk // 16, 64))
                ext = b_cursor + np.cumsum(g)
                b_starts = np.concatenate([b_starts, ext])
                b_cursor = float(ext[-1])
            idx = np.searchsorted(b_starts, ts, side="right") - 1
            in_burst = (idx >= 0) & (ts < b_starts[np.maximum(idx, 0)]
                                     + burst_dur_s)
            lam = np.where(in_burst, lam * burst_mult, lam)
        accept = acc_rng.random(chunk) < lam / lam_max
        sel = ts[accept]
        out.append(sel[:need])
        got += min(len(sel), need)
    return np.concatenate(out)[:n_events]


# ------------------------------------------------------------ helpers

def mix64(x) -> np.ndarray:
    """splitmix64 finaliser over uint64 (vectorised)."""
    with np.errstate(over="ignore"):
        z = np.asarray(x, np.uint64) + np.uint64(_SALT)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _unit(h: np.ndarray) -> np.ndarray:
    """uint64 hash → uniform in (0, 1)."""
    return ((h >> np.uint64(11)).astype(np.float64) + 0.5) / float(1 << 53)


_INV = np.vectorize(NormalDist().inv_cdf, otypes=[float])


def lognormal_quantile(u, median: float, sigma: float, lo: int,
                       hi: int) -> np.ndarray:
    """Lognormal(ln median, sigma) at probabilities ``u``, rounded and
    clipped to [lo, hi]."""
    x = np.exp(np.log(median) + sigma * _INV(np.asarray(u, np.float64)))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def arrival_times(traffic: dict, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds). ``poisson``: exponential gaps at their
    quantiles in a seeded order, scaled so n = rate * seconds requests
    fill the window exactly. ``burst``: the copied NHPP sampler."""
    arr = traffic["arrivals"]
    if arr["kind"] == "poisson":
        n = max(1, int(round(traffic["rate_rps"] * seconds)))
        gaps = rng.permutation(-np.log1p(-stratified(n)))
        t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        return t * (seconds / gaps.sum())
    if arr["kind"] == "burst":
        peak = traffic["rate_rps"] * arr["burst_mult"]
        t = diurnal_burst_arrivals(
            rng, int(peak * seconds * 1.5) + 16, traffic["rate_rps"],
            peak_mult=1.0, burst_rate_per_s=arr["burst_rate_per_s"],
            burst_mult=arr["burst_mult"], burst_dur_s=arr["burst_dur_s"])
        return t[t < seconds]
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


# ------------------------------------------------------------ requests

@dataclass
class Traffic:
    """One run's requests, column-wise. ``cand_off[i]:cand_off[i+1]``
    slices request i's candidates."""
    due_s: np.ndarray
    user: np.ndarray
    item: np.ndarray
    cand_off: np.ndarray
    cand_ids: np.ndarray
    cand_scores: np.ndarray

    def __len__(self):
        return len(self.due_s)

    def candidates(self, i: int) -> tuple[np.ndarray, np.ndarray]:
        s = slice(self.cand_off[i], self.cand_off[i + 1])
        return self.cand_ids[s], self.cand_scores[s]


def _distinct_zipf(rng, c: int, vocab: int, a: float) -> np.ndarray:
    got = np.empty(0, np.int64)
    while len(got) < c:
        draw = np.concatenate([got, zipf_ids(rng, 2 * c, vocab, a)])
        _, first = np.unique(draw, return_index=True)
        got = draw[np.sort(first)]
    return got[:c]


def make_traffic(cfg: dict, traffic: dict, seed: int,
                 seconds: float) -> Traffic:
    rng = np.random.default_rng(seed)
    due = arrival_times(traffic, seconds, np.random.default_rng(
        rng.integers(0, 2**63)))
    n = len(due)
    a = traffic["zipf_a"]
    users = zipf_ids(rng, n, field(cfg, "user_fields", "user_id")["vocab"], a)
    item_vocab = field(cfg, "item_fields", "item_id")["vocab"]
    items = zipf_ids(rng, n, item_vocab, a)
    cd = traffic["candidates"]
    counts = rng.permutation(lognormal_quantile(
        stratified(n), cd["median"], cd["sigma"], cd["min"], cd["max"]))
    ids = [_distinct_zipf(rng, int(c), item_vocab, a) for c in counts]
    off = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return Traffic(due_s=due, user=users, item=items, cand_off=off,
                   cand_ids=np.concatenate(ids),
                   cand_scores=rng.random(int(off[-1])))


def field(cfg: dict, group: str, name: str) -> dict:
    return next(f for f in cfg[group] if f["name"] == name)


def side_ids(key: int, f: dict, salt: int) -> np.ndarray:
    """A field's ids as a function of the owning key alone: (bag,) ints."""
    k = np.full(f["bag"], key, np.uint64) << np.uint64(16)
    h = mix64(k | (np.uint64(salt) + np.arange(f["bag"], dtype=np.uint64)))
    return (h % np.uint64(f["vocab"])).astype(np.int64)


def user_history(cfg: dict, traffic: dict, uid: int) -> np.ndarray:
    """The user's behaviour history: (seq_len,) item ids, left-aligned,
    -1 padded. Its length is lognormal by the user's hash; its items are
    Zipf from a stream keyed by the user."""
    hd = traffic["history"]
    u = _unit(mix64(np.asarray([uid], np.uint64) ^ np.uint64(0x5EED)))[0]
    T = cfg["seq_len"]
    n = int(lognormal_quantile([u], hd["median"], hd["sigma"], hd["min"],
                               min(hd["max"], T))[0])
    out = np.full(T, -1, np.int64)
    rng = np.random.default_rng([0x4157, int(uid)])
    out[:n] = zipf_ids(rng, n, field(cfg, "item_fields", "item_id")["vocab"],
                       traffic["zipf_a"])
    return out


def user_fields(cfg: dict, uid: int) -> dict:
    """Every user field's ids for this user: ``user_id`` itself, the rest
    hashed from it."""
    out = {}
    for k, f in enumerate(cfg["user_fields"]):
        v = (np.asarray([uid]) if f["name"] == "user_id"
             else side_ids(uid, f, 101 + k))
        out[f["name"]] = v[0] if f["bag"] == 1 else v
    return out


def item_fields(cfg: dict, iid: int) -> dict:
    out = {}
    for k, f in enumerate(cfg["item_fields"]):
        v = (np.asarray([iid]) if f["name"] == "item_id"
             else side_ids(iid, f, 201 + k))
        out[f["name"]] = v[0] if f["bag"] == 1 else v
    return out
