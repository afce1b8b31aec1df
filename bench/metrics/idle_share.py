"""Share of the traced window in which no operation ran on the device,
in %: 1 minus the union of the device op intervals over the window."""


def read(w):
    return None if w.trace is None else 100.0 * w.trace["idle_share"]
