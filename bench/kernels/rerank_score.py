"""Counts for the Pallas ``rerank_score`` kernel (``kernels/rerank_score``):
the fused DIN local activation unit, pooling and score MLP over one
user's C candidates.

FLOPs and bytes are those of the useful work: the real candidates and
the valid history rows, in the fewest operations the algebra allows, so
that the count reads the same whatever implements it. Bytes are the least
the kernel has to move: every input once, the weights once, the scores
out.
"""
from __future__ import annotations

from jzb.manifest import load

#: HLO instruction names (numeric suffix dropped) of the kernel's device
#: op: the Pallas call is named after the jitted function that makes it
TRACE_NAMES = ("_rerank_score",)

_F32 = 4


def flops(mc, t: int, c: int) -> int:
    return load("models", "din").flops(mc, t, c)


def bytes_moved(mc, t: int, c: int) -> int:
    D = mc.embed_dim
    H1, H2 = mc.attn_mlp
    M1, M2 = mc.mlp
    d_u = len(mc.user_fields) * D
    d_i = (len(mc.item_fields) - 1) * D
    d_in = 2 * D + d_u + d_i
    weights = (4 * D * H1 + H1 + H1 * H2 + H2 + H2 + 1
               + d_in * M1 + M1 + M1 * M2 + M2 + M2 + 1)
    inputs = t * D + t + c * D + c * d_i + d_u
    return _F32 * (weights + inputs + c)
