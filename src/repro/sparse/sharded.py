"""Row-sharded embedding tables — the on-TPU distributed sparse parameter cube.

The paper's cube is a distributed read-only KV store over feature signatures
(§5.1). On a pod the same role is played by row-sharding each table over the
``model`` mesh axis; a lookup is a shard_map: every device takes the rows it
owns (masked take) and the results are summed over the axis (psum) — each row
lives on exactly one shard, so the psum reconstructs the gather. The
collective is only (batch × dim), never a table transfer.

Differentiable: grad w.r.t. the table is the masked scatter-add of the
incoming cotangents on the owning shard (psum's transpose is identity
broadcast), i.e. exactly the sparse gradient a parameter server would apply.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import runtime

SHARD_AXIS = "model"


def table_spec_sharded() -> P:
    return P(SHARD_AXIS, None)


def _local_lookup(table_shard: jax.Array, ids: jax.Array, rows_per_shard: int) -> jax.Array:
    shard_idx = jax.lax.axis_index(SHARD_AXIS)
    local = ids - shard_idx * rows_per_shard
    ok = (local >= 0) & (local < rows_per_shard)
    vecs = jnp.take(table_shard, jnp.where(ok, local, 0), axis=0, mode="clip")
    vecs = vecs * ok[..., None].astype(vecs.dtype)
    return jax.lax.psum(vecs, SHARD_AXIS)


def sharded_lookup(table: jax.Array, ids: jax.Array) -> jax.Array:
    """ids (...,) int32 → (..., D), table rows sharded over ``model``.

    Falls back to a dense take when no >1 ``model`` axis is installed, so the
    same model code runs in smoke tests (1 device) and on the pod.
    """
    mesh = runtime.current_mesh()
    if mesh is None or mesh.shape.get(SHARD_AXIS, 1) == 1:
        return jnp.take(table, ids, axis=0, mode="clip")
    n_shards = mesh.shape[SHARD_AXIS]
    vocab = table.shape[0]
    if vocab % n_shards != 0:
        # Small tables (e.g. SchNet atom types) are not worth sharding.
        return jnp.take(table, ids, axis=0, mode="clip")
    rows_per_shard = vocab // n_shards

    # Replicate ids when the leading dim can't shard the data axes (e.g.
    # batch-1 decode) — the psum('model') path is identical either way.
    shardable = (ids.ndim >= 1 and ids.shape[0] % runtime.data_axis_size() == 0
                 and ids.shape[0] >= runtime.data_axis_size())
    lead = P(runtime.batch_axes()) if shardable else P(None)
    id_spec = P(*(lead + (None,) * (ids.ndim - 1)))
    out_spec = P(*(lead + (None,) * ids.ndim))

    fn = jax.shard_map(
        lambda t, i: _local_lookup(t, i, rows_per_shard),
        mesh=mesh,
        in_specs=(P(SHARD_AXIS, None), id_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(table, ids)


def _donate_argnums() -> tuple:
    """Donate the table buffer where the backend can actually alias it (TPU/
    GPU); CPU donation is unimplemented in XLA and would only warn-spam."""
    return (0,) if jax.default_backend() in ("tpu", "gpu") else ()


@functools.lru_cache(maxsize=None)
def _row_update_fn(mesh, rows_per_shard: int):
    if mesh is None:
        return jax.jit(lambda t, i, r: t.at[i].set(r, mode="drop"),
                       donate_argnums=_donate_argnums())

    def local(t, i, r):
        shard_idx = jax.lax.axis_index(SHARD_AXIS)
        local_ids = i - shard_idx * rows_per_shard
        # mode="drop" alone is NOT the ownership mask: drop applies AFTER
        # negative-index normalization, so a row owned by an EARLIER shard
        # (negative local id) would wrap into this shard's tail and
        # silently overwrite another key's parameters. Push non-owned ids
        # past the end instead — those genuinely drop.
        ok = (local_ids >= 0) & (local_ids < rows_per_shard)
        safe = jnp.where(ok, local_ids, rows_per_shard)
        return t.at[safe].set(r, mode="drop")

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(SHARD_AXIS, None), P(None),
                                 P(None, None)),
                       out_specs=P(SHARD_AXIS, None), check_vma=False)
    return jax.jit(fn, donate_argnums=_donate_argnums())


def sharded_row_update(table: jax.Array, ids: jax.Array,
                       rows: jax.Array) -> jax.Array:
    """In-place row updates of the HBM head: scatter ``rows`` into ``table``
    at ``ids`` with the table buffer DONATED, so XLA writes the touched rows
    into the existing allocation — the streaming-update path (DESIGN.md §6)
    migrates hot rows from the cube tail into a live multi-GB head without
    a table rebuild or a second table's worth of HBM. Under a >1 ``model``
    mesh axis the scatter runs per shard inside shard_map (each device
    updates only the rows it owns; ids are replicated — they're int32 and
    tiny). Returns the updated table; the input reference is consumed where
    donation is in effect. Duplicate ids within one call are the caller's
    to resolve (the update policy dedups, last-wins, before calling)."""
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.asarray(rows, table.dtype)
    if ids.size == 0:
        return table
    mesh = runtime.current_mesh()
    n_shards = 1 if mesh is None else mesh.shape.get(SHARD_AXIS, 1)
    vocab = table.shape[0]
    if mesh is None or n_shards == 1 or vocab % n_shards != 0:
        return _row_update_fn(None, 0)(table, ids, rows)
    return _row_update_fn(mesh, vocab // n_shards)(table, ids, rows)


def sharded_embedding_bag(table: jax.Array, ids: jax.Array,
                          weights: Optional[jax.Array] = None,
                          combiner: str = "sum") -> jax.Array:
    """Padded multi-hot bag over a row-sharded table: ids (B, K) → (B, D)."""
    vecs = sharded_lookup(table, ids)          # (B, K, D)
    if weights is None:
        w = jnp.ones(ids.shape, dtype=vecs.dtype)
    else:
        w = weights.astype(vecs.dtype)
    out = jnp.einsum("bk,bkd->bd", w, vecs)
    if combiner == "mean":
        out = out / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return out


# --------------------------------------------------------------------------
# 2-D row sharding: rows over the flattened ("data","model") axes — needed
# for TB-scale tables (JiZHI Table 1: 210–500 GB/service; our two-tower is
# ~0.5 TB fp32 → 2 GB/chip over 256 chips). The bag is POOLED LOCALLY before
# any collective, so comm is O(B×D) (a psum_scatter + psum), never O(B×K×D)
# and never a table transfer — this is the cube-access pattern on ICI.
# --------------------------------------------------------------------------

BIG_AXES = ("data", "model")


def sharded_gather_a2a(table: jax.Array, ids: jax.Array,
                       cap_factor: float = 4.0) -> jax.Array:
    """Single-id lookup over a 2-D row-sharded table via ALL-TO-ALL exchange.

    The psum-based path dense-sums (N, D) partials that are zero everywhere
    except each id's owner — ~2 orders of magnitude more ICI traffic than
    the information moved. This is the DLRM/TPU-embedding exchange instead:

      1. all-gather the int32 ids over both axes (N×4 bytes — tiny);
      2. every device packs the rows IT OWNS into per-destination buckets
         (destination = the id's position shard), capacity-padded;
      3. one all_to_all moves each row exactly once;
      4. receivers scatter rows into their (N_loc, D) output slice.

    Comm per device ≈ n_shards·cap·D ≈ cap_factor × the information-
    theoretic minimum, vs (g−1)·N_loc·D·g for the psum path.
    Capacity: ids here index positions uniformly across shards, so bucket
    occupancy is Poisson(N/g²); cap_factor=4 makes overflow vanishingly
    rare (overflowed rows fall back to zero — bound checked by tests).
    [§Perf iteration 5 — beyond-paper optimization]
    """
    mesh = runtime.current_mesh()
    if mesh is None or mesh.shape.get("model", 1) * mesh.shape.get("data", 1) == 1:
        return jnp.take(table, ids, axis=0, mode="clip")
    n_data = mesh.shape.get("data", 1)
    n_model = mesh.shape.get("model", 1)
    g = n_data * n_model
    vocab, D = table.shape
    if vocab % g:
        return sharded_embedding_bag_2d(table, ids[:, None])
    orig_n = ids.shape[0]
    pad = (-orig_n) % g
    if pad:
        ids = jnp.pad(ids, (0, pad))
    N = ids.shape[0]
    rows = vocab // g
    n_loc = N // g
    cap = max(8, int(np.ceil(cap_factor * N / (g * g) / 8)) * 8)

    def local(t, i):
        di = jax.lax.axis_index("data")
        mi = jax.lax.axis_index("model")
        shard = di * n_model + mi
        ig = jax.lax.all_gather(i, ("data", "model"), axis=0, tiled=True)
        local_ids = ig - shard * rows
        mine = (local_ids >= 0) & (local_ids < rows)
        dest = (jnp.arange(N, dtype=jnp.int32) // n_loc)
        # dest is MONOTONE in position, so rank-in-bucket is a block-wise
        # exclusive cumsum — no sort needed [§Perf iteration 6]
        mine_i = mine.astype(jnp.int32)
        excl = jnp.cumsum(mine_i) - mine_i              # exclusive count
        start_excl = jnp.take(excl, dest * n_loc)       # count before block
        pos = excl - start_excl
        keep = mine & (pos < cap)
        slot = jnp.where(keep, dest * cap + pos, g * cap)
        # slot → local row index, THEN gather straight into the buckets —
        # never materializes an (N, D) dense intermediate (same discipline
        # as the MoE dispatch)
        idx_buf = jnp.zeros((g * cap + 1,), jnp.int32).at[slot].set(
            jnp.clip(local_ids, 0, rows - 1).astype(jnp.int32))
        occ = jnp.zeros((g * cap + 1,), t.dtype).at[slot].max(
            keep.astype(t.dtype))
        buckets = jnp.take(t, idx_buf[: g * cap], axis=0) \
            * occ[: g * cap, None]
        posn = jnp.full((g * cap + 1,), -1, jnp.int32).at[slot].set(
            jnp.where(keep, jnp.arange(N, dtype=jnp.int32) % n_loc, -1))
        buckets = buckets.reshape(g, cap, D)
        posn = posn[: g * cap].reshape(g, cap)
        # one row moves exactly once
        recv = jax.lax.all_to_all(buckets, ("data", "model"), 0, 0,
                                  tiled=True)          # (g*cap, D)
        rpos = jax.lax.all_to_all(posn, ("data", "model"), 0, 0, tiled=True)
        out = jnp.zeros((n_loc + 1, D), t.dtype)
        out = out.at[jnp.where(rpos.reshape(-1) >= 0, rpos.reshape(-1),
                               n_loc)].add(recv.reshape(-1, D))
        return out[:n_loc]

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(BIG_AXES, None), P(BIG_AXES)),
                       out_specs=P(BIG_AXES, None), check_vma=False)
    out = fn(table, ids)
    return out[:orig_n] if pad else out


def table_spec_2d() -> P:
    return P(BIG_AXES, None)


def sharded_embedding_bag_2d(table: jax.Array, ids: jax.Array,
                             weights: Optional[jax.Array] = None,
                             combiner: str = "sum",
                             comm_dtype=None) -> jax.Array:
    """ids (B, K) → (B, D); table rows sharded over ("data","model").

    Inside shard_map: all-gather the (tiny, int32) ids over "data", pool each
    device's owned rows into a partial (B_row, D), then psum_scatter("data")
    + psum("model") reassembles exact bag sums on the batch owners.

    comm_dtype (e.g. bf16) downcasts the pooled partials before the
    collectives — halves ICI traffic on serving paths where bf16 pooled
    embeddings are ample precision [§Perf iteration 4].
    """
    mesh = runtime.current_mesh()
    squeeze = ids.ndim == 1
    if squeeze:
        ids = ids[:, None]
        weights = None if weights is None else weights[:, None]
    if mesh is None or mesh.shape.get("model", 1) * mesh.shape.get("data", 1) == 1:
        from repro.sparse.embedding import embedding_bag_padded
        return embedding_bag_padded(table, ids, weights, combiner)
    n_data = mesh.shape.get("data", 1)
    n_model = mesh.shape.get("model", 1)
    n_shards = n_data * n_model
    vocab = table.shape[0]
    assert vocab % n_shards == 0, f"vocab {vocab} vs {n_shards} shards"
    rows = vocab // n_shards
    B = ids.shape[0]
    batch_axes = runtime.batch_axes()
    scatterable = (B % runtime.data_axis_size()) == 0 and B >= runtime.data_axis_size()

    D = table.shape[1]
    K = ids.shape[1]

    def local(t, i, w):
        # flat shard index: data-major over ("data","model")
        di = jax.lax.axis_index("data")
        mi = jax.lax.axis_index("model")
        shard = di * n_model + mi
        if scatterable:
            i = jax.lax.all_gather(i, "data", axis=0, tiled=True)
            w = jax.lax.all_gather(w, "data", axis=0, tiled=True)

        def pool(iw):
            ic, wc = iw
            local_ids = ic - shard * rows
            ok = (local_ids >= 0) & (local_ids < rows)
            vecs = jnp.take(t, jnp.where(ok, local_ids, 0), axis=0,
                            mode="clip")
            wv = wc.astype(vecs.dtype) * ok.astype(vecs.dtype)
            return jnp.einsum("bk,bkd->bd", wv, vecs), wv.sum(-1)

        # the (B_row, K, D) gather can dominate peak memory at bulk-serving
        # batches (262k × 50 × 256 ≈ 13 GB) — chunk it through lax.map
        B_row = i.shape[0]
        if B_row * K * D > (1 << 26):
            n_ch = 1
            target = max(1, (1 << 24) // max(1, K * D))
            while B_row % (n_ch * 2) == 0 and B_row // n_ch > target:
                n_ch *= 2
            part, cnt = jax.lax.map(
                pool, (i.reshape(n_ch, -1, K), w.reshape(n_ch, -1, K)))
            part = part.reshape(B_row, -1)
            cnt = cnt.reshape(B_row)
        else:
            part, cnt = pool((i, w))
        out_dtype = part.dtype
        if comm_dtype is not None:
            part = part.astype(comm_dtype)
        if scatterable:
            part = jax.lax.psum_scatter(part, "data", scatter_dimension=0, tiled=True)
            part = jax.lax.psum(part, "model")
            cnt = jax.lax.psum_scatter(cnt, "data", scatter_dimension=0, tiled=True)
            cnt = jax.lax.psum(cnt, "model")
        else:
            part = jax.lax.psum(part, ("data", "model"))
            cnt = jax.lax.psum(cnt, ("data", "model"))
        part = part.astype(out_dtype)
        if combiner == "mean":
            part = part / jnp.maximum(cnt, 1e-9)[:, None]
        return part

    if weights is None:
        weights = jnp.ones(ids.shape, jnp.float32)
    id_spec = P(batch_axes, None) if scatterable else P(None, None)
    out_spec = P(batch_axes, None) if scatterable else P(None, None)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(BIG_AXES, None), id_spec, id_spec),
                       out_specs=out_spec, check_vma=False)
    return fn(table, ids, weights)
