"""Device time per step (ms), averaged over the devices, in which a
collective op runs and no other op does: the exchange's time that the
step does not hide (the paper's "hidden or not").  Ops that span others
(``while``, ``call``, ``conditional``) cover nothing."""

from harness.trace import CONTAINERS, length, union


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    exposed = 0.0
    for ops in run.trace.devices.values():
        ops = [o for o in ops if o.opcode not in CONTAINERS]
        others = union((o.start, o.end) for o in ops if not o.collective)
        every = union((o.start, o.end) for o in ops)
        # The collectives' time outside every other op.
        exposed += length(every) - length(others)
    return 1e-6 * exposed / len(run.trace.devices) / run.window["units"]
