"""Device time per step (ms) in the hidden step's interior: the ops under
the program's ``hide.interior`` scope (``core/hide.py``): the interior
slice, its kernel and its write into the field."""

from harness import scopes


def read(run):
    return scopes.scope_ms(run, "hide.interior")
