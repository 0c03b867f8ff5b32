"""Share (%) of the traced window with no op on the device."""

from harness import readers


def read(run):
    return readers.idle_share(run)
