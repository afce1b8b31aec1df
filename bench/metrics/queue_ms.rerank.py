"""Mean time a request waited at the model stage, in ms: its queue span
(channel enqueue to dequeue) plus its assemble span (dequeue to batch
dispatch), from the program's tracer spans of every re-ranked request."""


def read(w):
    waits = [sum(s["t1"] - s["t0"] for s in w.spans(i, "rerank")
                 if s["kind"] in ("queue", "assemble"))
             for i in w.reranked()]
    waits = [x for x in waits if x > 0]
    return 1e3 * sum(waits) / len(waits) if waits else None
