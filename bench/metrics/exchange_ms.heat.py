"""Device time per step (ms) in the halo exchange: the ops under the
program's ``halo.update`` scope (``core/halo.py``), the innermost scope
under ``hide.exchange``: the face slices sent, the collective-permutes and
the writes of the received halos into the field."""

from harness import scopes


def read(run):
    return scopes.scope_ms(run, "halo.update")
