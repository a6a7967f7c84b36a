"""Time per MD step in the program's neighbor-list span (``repro.md.nlist``:
every replica's ``VerletNeighborList.update``, rebuilds included), ms,
from the traced window's ``repro.md.step`` markers."""
from benchlib import spans


def read(r):
    return spans.per_step_ms(r, "repro.md.nlist", "repro.md.step")
