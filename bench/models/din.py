"""Plain float32 reference of DIN (arXiv:1706.06978), and the weights the
benchmark serves it with.

Written from the paper, not from the program: the local activation unit
scores each history item against the target with an MLP over
``[h, t, h - t, h * t]`` (silu hiddens, linear out), the pooled interest
is the activation-weighted sum of the history without softmax (paper
§4.3), and the score MLP reads ``[pooled, target, user fields, item side
fields]``. Every matmul goes through ``mm`` so that the control can run
the same arithmetic at a lower precision. The weights use the program's
parameter layout so that the program can serve them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jzb.nets import bag_embed, dense_stack, mlp_apply, split


def init(key, mc) -> dict:
    ks = split(key, 3)
    D = mc.embed_dim
    d_other = (len(mc.user_fields) + len(mc.item_fields) - 1) * D
    return {"tables": tables(ks[0], mc),
            "attn_mlp": dense_stack(ks[1], 4 * D, mc.attn_mlp + (1,), mc),
            "mlp": dense_stack(ks[2], 2 * D + d_other, mc.mlp + (1,), mc)}


def tables(key, mc) -> dict:
    fields = mc.user_fields + mc.item_fields
    ks = split(key, len(fields))
    return {f.name: jax.random.normal(k, (f.vocab, mc.embed_dim),
                                      jnp.float32) * mc.table_std
            for f, k in zip(fields, ks)}


def logits(params, mc, user: dict, hist, target_ids, target_side: dict, mm):
    """One user against N targets. ``user``: field name → (bag,) ids;
    ``hist``: (T,) item ids, -1 padded; ``target_ids``: (N,);
    ``target_side``: side field name → (N, bag) ids. Returns (N,)."""
    tab = params["tables"]
    mask = (hist >= 0).astype(jnp.float32)
    h = tab["item_id"][jnp.maximum(hist, 0)] * mask[:, None]     # (T, D)
    t = tab["item_id"][target_ids]                               # (N, D)
    N, T, D = t.shape[0], h.shape[0], h.shape[1]
    hb = jnp.broadcast_to(h[None], (N, T, D))
    tb = jnp.broadcast_to(t[:, None], (N, T, D))
    feat = jnp.concatenate([hb, tb, hb - tb, hb * tb], -1)
    w = mlp_apply(params["attn_mlp"], feat.reshape(N * T, 4 * D), mm)
    w = w.reshape(N, T) * mask[None]
    pooled = mm(w, h)                                            # (N, D)
    u = jnp.concatenate([bag_embed(tab[f.name], user[f.name], f)
                         for f in mc.user_fields])
    side = [bag_embed(tab[f.name], target_side[f.name], f)
            for f in mc.side_item_fields]
    x = jnp.concatenate([pooled, t, jnp.broadcast_to(u, (N, u.shape[0]))]
                        + side, -1)
    return mlp_apply(params["mlp"], x, mm)[:, 0]


def attention_flops(mc, t: int, n: int) -> int:
    """Matmul FLOPs of the local activation unit and pooling for n targets
    against t valid history rows, in the fewest operations the algebra
    allows: the first layer's history block ``h @ (Wa + Wc)`` is shared by
    every target and ``t @ (Wb - Wc)`` is one row per target; only
    ``(h * t) @ Wd`` is per (target, row)."""
    D = mc.embed_dim
    H1, H2 = mc.attn_mlp
    shared = 2 * t * D * H1
    per_target = 2 * D * H1 + t * (2 * D * H1 + 2 * H1 * H2 + 2 * H2) \
        + 2 * t * D
    return shared + n * per_target


def score_mlp_flops(mc, n: int) -> int:
    D = mc.embed_dim
    d_in = 2 * D + (len(mc.user_fields) + len(mc.item_fields) - 1) * D
    M1, M2 = mc.mlp
    return n * 2 * (d_in * M1 + M1 * M2 + M2)


def flops(mc, t: int, n: int) -> int:
    """Matmul FLOPs to score n targets for one user with t valid history
    rows (the work the model needs; padding is not counted)."""
    return attention_flops(mc, t, n) + score_mlp_flops(mc, n)
