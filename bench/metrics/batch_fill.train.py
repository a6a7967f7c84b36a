"""Real bonds over capacity bonds of the batches the window's steps
consumed, in percent (a host count from the batch masks)."""


def read(r):
    real, cap = r.get("fill.train", (0, 0))
    return 100.0 * real / cap if cap else None
