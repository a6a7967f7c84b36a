"""Real bonds over capacity bonds of the replica groups ``BatchedMD.step``
packed in the window, in percent (a host count from the batch masks)."""


def read(r):
    real, cap = r.get("fill.md", (0, 0))
    return 100.0 * real / cap if cap else None
