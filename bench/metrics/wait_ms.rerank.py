"""Time of the model stage's ``wait`` phase per request it handled, in ms:
blocking on the device's answers (``np.asarray`` of each jitted call's
results). From the program's phase spans (``obs.trace.phase``), each
batch's span once, over the requests of ``exec_ms.rerank``."""
from jzb.spans import phase_ms


def read(w):
    return phase_ms(w, "rerank", "wait")
