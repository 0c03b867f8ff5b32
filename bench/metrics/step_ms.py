"""Whole window over the steps completed (ms), host clock."""

from harness import readers


def read(run):
    return readers.per_unit_ms(run)
