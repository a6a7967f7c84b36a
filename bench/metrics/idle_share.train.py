"""Share of the traced training window in which no operation ran on the
device (1 - busy union over the window, averaged over chips), percent."""


def read(r):
    return None if r.get("trace") is None else 100.0 * r["trace"]["idle_share"]
