"""Time of the model stage's ``post`` phase per request it handled, in ms:
each request's sigmoid and top-k list, and the batch's query-cache
insert and staleness guard. From the program's phase spans
(``obs.trace.phase``), each batch's span once, over the requests of
``exec_ms.rerank``."""
from jzb.spans import phase_ms


def read(w):
    return phase_ms(w, "rerank", "post")
