"""Time per training step in which a collective (the gradient all-reduce
between chips) ran on a chip with no other operation beside it, ms,
averaged over the chips; None where the trace holds no collective."""


def read(r):
    tr = r.get("trace")
    if tr is None or not tr["collective_ops"]:
        return None
    return 1e3 * tr["exposed_collective_s_per_step"]
