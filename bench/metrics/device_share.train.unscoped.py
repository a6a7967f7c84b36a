"""Share of the traced training window's device op time that no program
scope names (what the scopes leave uncovered), percent, averaged over
the chips; None where no operation names a program scope."""
from benchlib import spans


def read(r):
    return spans.device_share(r, "unscoped")
