"""Time per training step the step loop waited for its next batch (the
program's ``repro.data.wait`` span around the ``Prefetcher``'s queue), ms,
from the traced window's ``repro.train.step`` markers."""
from benchlib import spans


def read(r):
    return spans.per_step_ms(r, "repro.data.wait", "repro.train.step")
