"""Device time of the model stage's programs (``serve_scores`` and
``score_candidates``) per request the stage scored inside the traced
window, in ms, from the profiler trace."""


def read(w):
    if w.trace is None:
        return None
    n = sum(1 for i in w.reranked() if w.in_trace(i))
    return 1e3 * w.trace["module_s"] / n if n and w.trace["module_s"] else None
