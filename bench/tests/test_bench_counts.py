"""FLOP and byte counts of the benchmark, checked against counts made by
hand at the published shapes (DIN: embed 18, attention 80-40, MLP
200-80; DIEN: GRU 108; two user and two item fields; 40 valid history
rows, 64 candidates)."""
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from jzb.config import model_cfg  # noqa: E402
from jzb.manifest import load  # noqa: E402


def _mc(name):
    return model_cfg(json.loads((BENCH / "configs" / f"{name}.json")
                                .read_text()))


def test_din_flops_by_hand():
    mc = _mc("din-rerank")
    # first layer, history block once: 2*40*18*80
    shared = 115_200
    # per candidate: t@(Wb-Wc) 2*18*80, per row (h*t)@Wd 2*18*80 +
    # 80->40 2*80*40 + 40->1 2*40, pooling 2*40*18
    per = 2_880 + 40 * (2_880 + 6_400 + 80) + 1_440
    mlp = 2 * (90 * 200 + 200 * 80 + 80)       # [pooled,t,u(36),i(18)]
    din = load("models", "din")
    assert din.attention_flops(mc, 40, 64) == shared + 64 * per
    assert din.flops(mc, 40, 64) == 28_715_520 == shared + 64 * (per + mlp)
    assert din.flops(mc, 40, 1) == shared + per + mlp


def test_dien_flops_by_hand():
    mc = _mc("dien-rerank")
    gru = 40 * 2 * (18 * 324 + 108 * 324)       # GRU over 40 steps
    shared = gru + 40 * 2 * 108 * 18 + 40 * 2 * 108 * 324
    per = 40 * 2 * 18 + 40 * 2 * 108 * 324 + 2 * (180 * 200 + 200 * 80 + 80)
    dien = load("models", "dien")
    assert dien.flops(mc, 40, 64) == 192_138_240 == shared + 64 * per


def test_rerank_score_kernel_counts_by_hand():
    mc = _mc("din-rerank")
    k = load("kernels", "rerank_score")
    weights = (72 * 80 + 80 + 80 * 40 + 40 + 40 + 1
               + 90 * 200 + 200 + 200 * 80 + 80 + 80 + 1)
    assert weights == 43_482
    inputs = 40 * 18 + 40 + 64 * 18 + 64 * 18 + 36
    assert k.bytes_moved(mc, 40, 64) == 4 * (weights + inputs + 64) \
        == 186_584
    assert k.flops(mc, 40, 64) == load("models", "din").flops(mc, 40, 64)


def test_padding_is_not_work():
    """Counts grow with the real sizes only: one more candidate costs one
    candidate's work, one more history row one row's."""
    mc = _mc("din-rerank")
    din = load("models", "din")
    per_c = din.flops(mc, 40, 65) - din.flops(mc, 40, 64)
    assert per_c == din.flops(mc, 40, 2) - din.flops(mc, 40, 1)
    per_t = din.flops(mc, 41, 1) - din.flops(mc, 40, 1)
    assert per_t == 2 * 18 * 80 + (2 * 18 * 80 + 2 * 80 * 40 + 2 * 40) \
        + 2 * 18
