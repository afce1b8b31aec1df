"""Share of answered requests the query cache answered, in %
(``Response.from_cache``)."""


def read(w):
    if not w.answered:
        return None
    hits = sum(1 for i in w.answered
               if w.events[i].meta["response"].from_cache)
    return 100.0 * hits / len(w.answered)
