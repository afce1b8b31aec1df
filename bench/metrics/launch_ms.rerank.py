"""Time of the model stage's ``launch`` phase per request it handled, in
ms: the jitted calls (the pointwise ``serve`` and each request's
candidate scorer), until each returns. From the program's phase spans
(``obs.trace.phase``), each batch's span once, over the requests of
``exec_ms.rerank``."""
from jzb.spans import phase_ms


def read(w):
    return phase_ms(w, "rerank", "launch")
