"""The model stage's share of the chip's peak, in %: the FLOPs the model
needs for every request the stage scored (one pointwise row and the kept
candidates, at the valid history length, no padding) over the stage's
wall time times the bf16 peak of the device kind."""


def read(w):
    st = w.stage("rerank")
    if not st or st.busy_s <= 0 or w.cell.peaks() is None:
        return None
    model, mc = w.cell.model, w.cell.mc
    flops = 0
    for i in w.reranked():
        t, c = w.work(i)
        flops += model.flops(mc, t, 1) + (model.flops(mc, t, c) if c else 0)
    peak = w.cell.peaks()["bf16_flops_per_s"]
    return 100.0 * flops / (st.busy_s * peak) if flops else None
