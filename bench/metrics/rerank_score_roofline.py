"""Share of its roofline that the Pallas ``rerank_score`` kernel reached
in the traced window, in %: for each request re-ranked there, the least
time the chip could take (the larger of the kernel's FLOPs over the bf16
peak and its bytes over the HBM bandwidth, useful work only), summed,
over the kernel's device time in the trace."""
from jzb.manifest import load


def read(w):
    if w.trace is None or not w.trace["kernel_s"].get("rerank_score"):
        return None
    k = load("kernels", "rerank_score")
    pk = w.cell.peaks()
    best = 0.0
    for i in w.reranked():
        t, c = w.work(i)
        if c and w.in_trace(i):
            best += max(k.flops(w.cell.mc, t, c) / pk["bf16_flops_per_s"],
                        k.bytes_moved(w.cell.mc, t, c) / pk["hbm_bytes_per_s"])
    return 100.0 * best / w.trace["kernel_s"]["rerank_score"] if best else None
