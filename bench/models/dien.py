"""Plain float32 reference of DIEN (arXiv:1809.03672), and the weights the
benchmark serves it with.

Interest extraction is a GRU over the embedded history; interest
evolution is an AUGRU whose update gate is scaled by the target's
attention over the GRU states (softmax over the valid rows); the score
MLP reads ``[final state, target, user fields, item side fields]``. The
GRU keeps the convention ``h' = (1 - z) * n + z * h`` and the AUGRU the
paper's ``h' = (1 - a z) * h + a z * n``, as the served model states them.
Padded history rows come after the valid ones and carry zero attention,
so they leave the AUGRU state unchanged. Every matmul goes through
``mm``; the weights use the program's parameter layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jzb.manifest import load
from jzb.nets import bag_embed, dense_stack, mlp_apply, split

din = load("models", "din")


def _gru_init(key, d_in: int, h: int, mc) -> dict:
    k1, k2, k3 = split(key, 3)
    return {"w": jax.random.normal(k1, (d_in, 3 * h), jnp.float32)
            * d_in ** -0.5,
            "u": jax.random.normal(k2, (h, 3 * h), jnp.float32) * h ** -0.5,
            "b": jax.random.normal(k3, (3 * h,), jnp.float32) * mc.bias_std}


def init(key, mc) -> dict:
    ks = split(key, 5)
    D, H = mc.embed_dim, mc.gru_dim
    d_other = (len(mc.user_fields) + len(mc.item_fields) - 1) * D
    return {"tables": din.tables(ks[0], mc),
            "gru": _gru_init(ks[1], D, H, mc),
            "augru": _gru_init(ks[2], H, H, mc),
            "att_w": jax.random.normal(ks[3], (H, D), jnp.float32)
            * H ** -0.5,
            "mlp": dense_stack(ks[4], H + D + d_other, mc.mlp + (1,), mc)}


def _gates(p, gx, h, mm):
    gh = mm(h, p["u"])
    H = h.shape[-1]
    r = jax.nn.sigmoid(gx[..., :H] + gh[..., :H])
    z = jax.nn.sigmoid(gx[..., H:2 * H] + gh[..., H:2 * H])
    n = jnp.tanh(gx[..., 2 * H:] + r * gh[..., 2 * H:])
    return z, n


def logits(params, mc, user: dict, hist, target_ids, target_side: dict, mm):
    """One user against N targets; arguments as ``din.logits``."""
    tab = params["tables"]
    H = mc.gru_dim
    mask = (hist >= 0).astype(jnp.float32)
    h = tab["item_id"][jnp.maximum(hist, 0)] * mask[:, None]     # (T, D)
    t = tab["item_id"][target_ids]                               # (N, D)
    N = t.shape[0]
    gru = params["gru"]
    gx = mm(h, gru["w"]) + gru["b"]                              # (T, 3H)

    def gru_step(s, gx_t):
        z, n = _gates(gru, gx_t[None], s, mm)
        s = (1 - z) * n + z * s
        return s, s[0]

    _, states = jax.lax.scan(gru_step, jnp.zeros((1, H)), gx)    # (T, H)
    att = mm(t, mm(states, params["att_w"]).T)                   # (N, T)
    att = jax.nn.softmax(jnp.where(mask[None] > 0, att, -1e30), -1)
    att = att * mask[None]
    aug = params["augru"]
    ax = mm(states, aug["w"]) + aug["b"]                         # (T, 3H)

    def augru_step(s, xs):
        ax_t, a_t = xs
        z, n = _gates(aug, jnp.broadcast_to(ax_t, (N, 3 * H)), s, mm)
        z = z * a_t[:, None]
        return (1 - z) * s + z * n, None

    final, _ = jax.lax.scan(augru_step, jnp.zeros((N, H)), (ax, att.T))
    u = jnp.concatenate([bag_embed(tab[f.name], user[f.name], f)
                         for f in mc.user_fields])
    side = [bag_embed(tab[f.name], target_side[f.name], f)
            for f in mc.side_item_fields]
    x = jnp.concatenate([final, t, jnp.broadcast_to(u, (N, u.shape[0]))]
                        + side, -1)
    return mlp_apply(params["mlp"], x, mm)[:, 0]


def flops(mc, t: int, n: int) -> int:
    """Matmul FLOPs to score n targets for one user with t valid history
    rows: the GRU and the AUGRU's input projection once, the attention,
    the AUGRU recurrence and the score MLP per target."""
    D, H = mc.embed_dim, mc.gru_dim
    gru = t * 2 * (D * 3 * H + H * 3 * H)
    shared = gru + t * 2 * H * D + t * 2 * H * 3 * H
    per_target = t * 2 * D + t * 2 * H * 3 * H
    d_in = H + D + (len(mc.user_fields) + len(mc.item_fields) - 1) * D
    M1, M2 = mc.mlp
    return shared + n * (per_target + 2 * (d_in * M1 + M1 * M2 + M2))
