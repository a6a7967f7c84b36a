"""Share of the traced training window's device op time under the
readout heads' scope (``readout``), percent, averaged over the chips;
None where no operation names a program scope."""
from benchlib import spans


def read(r):
    return spans.device_share(r, "readout")
