"""Share of the traced training window's device op time under the
interaction blocks' scopes (``block<i>``, ``final_block``), percent,
averaged over the chips; None where no operation names a program scope."""
from benchlib import spans


def read(r):
    return spans.device_share(r, "blocks")
