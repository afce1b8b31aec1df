"""Share of offered candidates the shed stage kept for the re-rank, in %
(``OnlineShedder.state`` over the window)."""


def read(w):
    sh = w.cell.shedder_state
    n = sh.kept_events + sh.shed_events if sh else 0
    return 100.0 * sh.kept_events / n if n else None
