"""Device time per step (ms) outside the heat kernel: slab copies,
updates of slices, permutes (``core/hide.py``, ``core/halo.py``)."""

from harness import readers


def read(run):
    return readers.outside_kernels_ms(run)
