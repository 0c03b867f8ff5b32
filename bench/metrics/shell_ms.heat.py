"""Device time per step (ms) in the hidden step's boundary shell: the
ops under the program's ``hide.shell`` scope (``core/hide.py``): slab
slices, slab kernels and the slab writes into the field."""

from harness import scopes


def read(run):
    return scopes.scope_ms(run, "hide.shell")
