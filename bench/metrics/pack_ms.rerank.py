"""Time of the model stage's ``pack`` phase per request it handled, in ms:
building the device inputs and putting them on the device: the pointwise
batch (bucket padding, ``pack_batch``) and each request's candidate set
(ids, compacted history, the puts). From the program's phase spans
(``obs.trace.phase``), each batch's span once, over the requests of
``exec_ms.rerank``."""
from jzb.spans import phase_ms


def read(w):
    return phase_ms(w, "rerank", "pack")
