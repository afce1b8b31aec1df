"""Expert-parallel MoE (DeepSeek-style: shared + fine-grained routed experts).

Dispatch is SORT-BASED (argsort by expert, rank-in-expert capacity, scatter
into (E_local, C, d) buffers) — linear memory and *actual* FLOPs, unlike the
GShard (T,E,C) one-hot einsum whose dispatch alone would dominate the
roofline at T=64k, E=256.

Distribution (inside one shard_map over the full mesh):
  * routed expert weights: experts over ``model``, d_ff over ``data``
    (2-D expert-weight sharding → deepseek-v3's 656B of expert weights cost
    5.2 GB/device, and dispatch never gathers a weight).
  * tokens: sharded over ("pod","data"); each MoE layer all-gathers tokens
    within its pod's data row, computes the f-slice of its local experts,
    then psum_scatter("data") + psum("model") combines f-partials and expert
    contributions back to token owners. MoE traffic never crosses pods.
  * shared experts are a plain dense GLU with standard TP (handled by the
    caller), not part of this file.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import runtime
from repro.models.layers import activation


def moe_expert_init(key, d_model: int, cfg, dtype) -> dict:
    """Routed experts + router. Weights stacked (E, d, f) / (E, f, d)."""
    E, f = cfg.n_routed, cfg.d_ff_expert
    ks = jax.random.split(key, 4)
    s_in, s_f = 1.0 / np.sqrt(d_model), 1.0 / np.sqrt(f)
    return {
        "router": (jax.random.normal(ks[0], (d_model, E), jnp.float32) * s_in
                   ).astype(jnp.float32),  # router kept fp32 (routing stability)
        "w1": (jax.random.normal(ks[1], (E, d_model, f), jnp.float32) * s_in).astype(dtype),
        "w3": (jax.random.normal(ks[2], (E, d_model, f), jnp.float32) * s_in).astype(dtype),
        "w2": (jax.random.normal(ks[3], (E, f, d_model), jnp.float32) * s_f).astype(dtype),
    }


def _capacity(tokens: int, cfg) -> int:
    c = int(np.ceil(cfg.capacity_factor * tokens * cfg.top_k / cfg.n_routed))
    return max(8, -(-c // 8) * 8)  # pad to sublane multiple


def _route(x, router_w, top_k: int):
    logits = (x.astype(jnp.float32) @ router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, top_k)                  # (T, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (Switch-style), returned for the training loss
    T, E = logits.shape
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros(E).at[idx.reshape(-1)].add(1.0) / (T * top_k)
    aux = E * jnp.sum(me * ce)
    return gate, idx, aux


def _dispatch_compute_combine(xg, gate, idx, w1, w3, w2, *, e0: int, C: int, act: str):
    """Sort-based pack → grouped GEMM → combine, for experts [e0, e0+E_loc).

    xg (T, d); gate/idx (T, k); w* (E_loc, d, f_loc)/(E_loc, f_loc, d).
    Returns (T, d) partial output (partial over f-slices when f is sharded).

    Memory discipline: the naive gather-by-pair materializes (T·k, d) — at
    deepseek-v3 scale that is 7.5 GB per layer. Instead we build a
    slot→token index map and gather straight into the (E_loc·C, d) capacity
    buffer, and combine with k separate (T, d) gathers (dropped pairs point
    at a zero sentinel row, so no extra masking is needed).
    """
    T, d = xg.shape
    k = idx.shape[1]
    E_loc = w1.shape[0]
    N = T * k
    e_flat = idx.reshape(-1) - e0                            # (N,)
    mine = (e_flat >= 0) & (e_flat < E_loc)
    sort_key = jnp.where(mine, e_flat, E_loc).astype(jnp.int32)
    order = jnp.argsort(sort_key, stable=True)
    sorted_e = sort_key[order]
    counts = jax.ops.segment_sum(jnp.ones((N,), jnp.int32), sorted_e,
                                 num_segments=E_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(N, dtype=jnp.int32) - starts[sorted_e]
    keep = (sorted_e < E_loc) & (pos < C)
    slot = jnp.where(keep, sorted_e * C + pos, E_loc * C)    # sentinel = last
    src_tok = (order // k).astype(jnp.int32)

    # slot → source token (occupancy via a parallel scatter of ones)
    idx_buf = jnp.zeros((E_loc * C + 1,), jnp.int32).at[slot].set(src_tok)
    occ = jnp.zeros((E_loc * C + 1,), xg.dtype).at[slot].max(
        keep.astype(xg.dtype))
    buf = jnp.take(xg, idx_buf[: E_loc * C], axis=0) \
        * occ[: E_loc * C, None]
    buf = buf.reshape(E_loc, C, d)

    h1 = jnp.einsum("ecd,edf->ecf", buf, w1)
    h3 = jnp.einsum("ecd,edf->ecf", buf, w3)
    h = activation(h1, act) * h3
    out_buf = jnp.einsum("ecf,efd->ecd", h, w2)              # f-partial
    flat = jnp.concatenate([out_buf.reshape(E_loc * C, d),
                            jnp.zeros((1, d), out_buf.dtype)])
    # token → its k slots (inverse permutation; dropped/foreign pairs hit
    # the zero sentinel row)
    slot_tok = jnp.zeros((N,), jnp.int32).at[order].set(
        jnp.where(keep, slot, E_loc * C)).reshape(T, k)
    out = jnp.zeros((T, d), xg.dtype)
    for j in range(k):                                       # k small (≤8)
        out = out + jnp.take(flat, slot_tok[:, j], axis=0) \
            * gate[:, j, None].astype(xg.dtype)
    return out


def moe_apply(p: dict, x: jax.Array, cfg, act: str = "silu"):
    """x (..., d) → (same, aux_loss). Token dims are flattened internally."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    mesh = runtime.current_mesh()
    ep = mesh is not None and mesh.shape.get("model", 1) > 1

    if not ep:
        gate, idx, aux = _route(xt, p["router"], cfg.top_k)
        out = _dispatch_compute_combine(
            xt, gate, idx, p["w1"], p["w3"], p["w2"],
            e0=0, C=_capacity(xt.shape[0], cfg), act=act)
        return out.reshape(*lead, d), aux

    n_model = mesh.shape["model"]
    n_data = mesh.shape.get("data", 1)
    assert cfg.n_routed % n_model == 0, "experts must divide the model axis"
    E_loc = cfg.n_routed // n_model
    batch_axes = runtime.batch_axes()
    T = xt.shape[0]
    # Tokens shard over the data axes when divisible (train/bulk serve);
    # tiny-token decode (e.g. batch-1 long-context) replicates tokens and the
    # psum over ("data","model") folds both the f-slice partials and the
    # expert contributions.
    tok_sharded = T % runtime.data_axis_size() == 0 and T >= runtime.data_axis_size()
    T_row = (T // runtime.data_axis_size()) * n_data if tok_sharded else T
    C = _capacity(T_row, cfg)
    f = cfg.d_ff_expert
    f_sharded = f % n_data == 0 and n_data > 1

    # chunk the gather+dispatch when the row buffer is large (v3: 940 MB/
    # layer): each chunk all-gathers T_row/n_ch tokens, dispatches into its
    # own capacity slice, computes, combines — MoE transients ÷ n_ch at the
    # cost of per-chunk (vs global) capacity drops [§Perf cell-1 iteration]
    d_model = xt.shape[-1]
    n_ch = 1
    while (T_row // n_ch) * d_model > (1 << 26) and \
            T_row % (n_ch * 2) == 0 and (T_row // (n_ch * 2)) % n_data == 0:
        n_ch *= 2
    C_ch = _capacity(T_row // n_ch, cfg)

    def local(xt_loc, router_w, w1, w3, w2):
        gate, idx, aux = _route(xt_loc, router_w, cfg.top_k)
        e0 = jax.lax.axis_index("model") * E_loc
        if tok_sharded and n_ch > 1:
            def chunk_fn(args):
                xc, gc, ic = args
                xg = jax.lax.all_gather(xc, "data", axis=0, tiled=True)
                gg = jax.lax.all_gather(gc, "data", axis=0, tiled=True)
                ig = jax.lax.all_gather(ic, "data", axis=0, tiled=True)
                return _dispatch_compute_combine(xg, gg, ig, w1, w3, w2,
                                                 e0=e0, C=C_ch, act=act)

            T_l = xt_loc.shape[0]
            outc = jax.lax.map(chunk_fn, (
                xt_loc.reshape(n_ch, T_l // n_ch, -1),
                gate.reshape(n_ch, T_l // n_ch, -1),
                idx.reshape(n_ch, T_l // n_ch, -1)))
            # each chunk's gather is (shard-major within the chunk); restore
            # the global gather order (shard, chunk, pos) for the combine
            out_full = outc.reshape(n_ch, n_data, T_l // n_ch, -1) \
                .transpose(1, 0, 2, 3).reshape(T_row, -1)
        elif tok_sharded:
            xg = jax.lax.all_gather(xt_loc, "data", axis=0, tiled=True)
            gg = jax.lax.all_gather(gate, "data", axis=0, tiled=True)
            ig = jax.lax.all_gather(idx, "data", axis=0, tiled=True)
            out_full = _dispatch_compute_combine(xg, gg, ig, w1, w3, w2,
                                                 e0=e0, C=C, act=act)
        else:
            out_full = _dispatch_compute_combine(xt_loc, gate, idx, w1, w3, w2,
                                                 e0=e0, C=C, act=act)
        if tok_sharded and T_row % (n_data * n_model) == 0:
            # combine = Σ over experts (model) and f-slices (data), then
            # return tokens to their data-shard owners. psum(model)+rs(data)
            # moves ≈2.9×|buf| on ICI; rs over BOTH axes then a small
            # all-gather(model) moves ≈1.06×|buf|  [§Perf iteration 2]
            out_tiny = jax.lax.psum_scatter(out_full, ("data", "model"),
                                            scatter_dimension=0, tiled=True)
            out_loc = jax.lax.all_gather(out_tiny, "model", axis=0,
                                         tiled=True)
        elif tok_sharded:
            out_full = jax.lax.psum(out_full, "model")
            out_loc = jax.lax.psum_scatter(out_full, "data",
                                           scatter_dimension=0, tiled=True)
        else:
            axes = ("data", "model") if f_sharded else ("model",)
            out_loc = jax.lax.psum(out_full, axes)
        return out_loc, jax.lax.pmean(aux, tuple(mesh.axis_names))

    w_spec_1 = P("model", None, "data" if f_sharded else None)
    w_spec_2 = P("model", "data" if f_sharded else None, None)
    tok_spec = P(batch_axes, None) if tok_sharded else P(None, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(tok_spec, P(None, None), w_spec_1, w_spec_1, w_spec_2),
        out_specs=(tok_spec, P()),
        check_vma=False)
    out, aux = fn(xt, p["router"], p["w1"], p["w3"], p["w2"])
    return out.reshape(*lead, d), aux


def moe_param_specs(cfg, f_sharded: bool) -> dict:
    """PartitionSpecs for one (unstacked) MoE layer's params."""
    fs = "data" if f_sharded else None
    return {"router": P(None, None),
            "w1": P("model", None, fs),
            "w3": P("model", None, fs),
            "w2": P("model", fs, None)}
