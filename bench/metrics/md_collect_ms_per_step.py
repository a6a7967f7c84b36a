"""Time per MD step in the program's collect span (``repro.md.collect``:
waiting for the device's forces and copying them to the host), ms, from
the traced window's ``repro.md.step`` markers."""
from benchlib import spans


def read(r):
    return spans.per_step_ms(r, "repro.md.collect", "repro.md.step")
