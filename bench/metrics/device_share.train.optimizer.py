"""Share of the traced training window's device op time under the
optimizer's scope (``optimizer``: clip, Adam, loss scaler), percent,
averaged over the chips; None where no operation names a program scope."""
from benchlib import spans


def read(r):
    return spans.device_share(r, "optimizer")
