"""jit'd wrapper for the fused re-rank scorer: pads to tile multiples and
dispatches to one of three implementations of the SAME fused algorithm
(shared-history first-layer decomposition + candidate streaming):

  * ``impl="pallas"`` — the Pallas kernel (compiled on TPU; the interpreter
    when ``interpret`` resolves True — parity/debug only, it is slow);
  * ``impl="xla"``    — the fused algorithm as blocked jnp: identical sums,
    no (C,T,4D) materialization; the serving default off-TPU;
  * ``impl=None``     — auto: "pallas" when ``repro.kernels.platform()``
    is "tpu", "xla" otherwise.

Callers hand the history ALREADY compacted/bucketed (serve/bucketing.py):
masked rows are exact no-ops, so scoring ``bucket(T_valid)`` rows is
bit-equal to scoring the full padded history — but skips its cost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels import pad_axis, resolve_interpret
from repro.kernels.rerank_score.kernel import rerank_score_pallas


def _fused_block_xla(hist, mask, tgt, uo, io,
                     a1, ab1, a2, ab2, a3, ab3, m1, mb1, m2, mb2, m3, mb3):
    """One candidate tile, same decomposition as the kernel body."""
    T, D = hist.shape
    BC = tgt.shape[0]
    wa, wb, wc, wd = a1[:D], a1[D:2 * D], a1[2 * D:3 * D], a1[3 * D:]
    ah = hist @ (wa + wc) + ab1                                 # (T,H1) shared
    bt = tgt @ (wb - wc)                                        # (BC,H1)
    ht = hist[None, :, :] * tgt[:, None, :]                     # (BC,T,D)
    h1 = (ht.reshape(BC * T, D) @ wd).reshape(BC, T, -1)
    x = jax.nn.silu(h1 + ah[None] + bt[:, None])
    x = jax.nn.silu(x.reshape(BC * T, -1) @ a2 + ab2)
    w = (x @ a3 + ab3).reshape(BC, T) * mask[None]
    pooled = w @ hist                                           # (BC,D)
    xx = jnp.concatenate(
        [pooled, tgt, jnp.broadcast_to(uo[None], (BC, uo.shape[0])), io], -1)
    s = jax.nn.silu(xx @ m1 + mb1)
    s = jax.nn.silu(s @ m2 + mb2)
    return (s @ m3 + mb3)[:, 0]


def rerank_score(hist, mask, target, user_other, item_other,
                 attn_mlp, score_mlp, block_c: int = 128,
                 impl: str | None = None, interpret: bool | None = None):
    """Score C candidates against one user's shared history in one fused
    pass.

    hist (T, D) embedded history, mask (T,), target (C, D) candidate
    embeddings, user_other (d_u,) user side features (NOT pre-broadcast),
    item_other (C, d_i) per-candidate side features; attn_mlp / score_mlp:
    3-layer towers as produced by ``mlp_tower_init`` (two silu hiddens +
    linear out). Returns per-candidate scores (C,) float32.

    Zero-pads T to 8 (masked → exact). The Pallas grid additionally pads C
    to ``block_c`` (scored and discarded); the XLA impl streams blocks of
    AT MOST ``block_c`` and never pads C — a 16-candidate bucket costs 16
    rows of work, not 128.
    """
    # the device decision is resolved here, outside the jit, so it is part
    # of the jit cache key and never replayed from a trace made under
    # another decision
    if impl is None:
        impl = "pallas" if kernels.platform() == "tpu" else "xla"
    return _rerank_score(hist, mask, target, user_other, item_other,
                         attn_mlp, score_mlp, block_c=block_c, impl=impl,
                         interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_c", "impl", "interpret"))
def _rerank_score(hist, mask, target, user_other, item_other,
                  attn_mlp, score_mlp, block_c: int, impl: str,
                  interpret: bool):
    assert len(attn_mlp) == 3 and len(score_mlp) == 3, \
        "fused path expects 2-hidden-layer towers (got " \
        f"{len(attn_mlp)}/{len(score_mlp)} layers)"
    C = target.shape[0]
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    hist_p = pad_axis(f32(hist), 8, 0)
    mask_p = pad_axis(f32(mask), 8, 0)
    uo = f32(user_other)
    weights = [f32(p[k]) for p in (*attn_mlp, *score_mlp) for k in ("w", "b")]

    if impl == "pallas":
        target_p = pad_axis(f32(target), block_c, 0)
        io_p = pad_axis(f32(item_other), block_c, 0)
        out = rerank_score_pallas(
            hist_p, mask_p, target_p, uo, io_p, *weights,
            block_c=block_c, interpret=interpret)[:C]
    elif impl == "xla":
        target_p, io_p = f32(target), f32(item_other)
        blocks = [
            _fused_block_xla(hist_p, mask_p, target_p[s:s + block_c],
                             uo, io_p[s:s + block_c], *weights)
            for s in range(0, C, block_c)]
        out = blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks)
    else:
        raise ValueError(f"unknown impl {impl!r}")
    return out
