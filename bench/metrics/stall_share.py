"""Share of the run that the whole process stood still, in %: the
program's ``process:stall`` spans (``obs.trace.StallMonitor``: a 5 ms tick
that woke 50 ms or more late) inside the span from the first request's
due time to the end of the drain, over that span's length. 0 where no
stall was recorded."""
from jzb.spans import stall_share


def read(w):
    return stall_share(w)
