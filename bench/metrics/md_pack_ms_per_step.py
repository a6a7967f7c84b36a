"""Time per MD step in the program's packing span (``repro.md.pack``: the
replica groups' ``batch_crystals``, host-to-device copy included), ms,
from the traced window's ``repro.md.step`` markers."""
from benchlib import spans


def read(r):
    return spans.per_step_ms(r, "repro.md.pack", "repro.md.step")
