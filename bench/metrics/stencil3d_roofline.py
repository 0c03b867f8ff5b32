"""Share (%) of the HBM roofline of the heat step's stencil: T read, Ci
read and T written once per step, over the peak bandwidth times the
stencil's device time.

That time is the Pallas kernels' (``kernels/stencil3d``) where the step
runs them.  Where it runs none, as where ``use_kernel="auto"`` takes the
XLA reference spelling (no x-block fits VMEM, as at 512^2 planes), it is
the device's busy time: a heat step without a kernel is its stencil and
the writes that spelling needs, so the same work reads against the same
bytes.  A ``[roofline]`` line on standard error names the time divided by
and gives both, so that a change that brings a kernel to such a cell can
be read against its parent's busy time as well as its own kernels'.
"""

import sys

from harness import readers


def read(run):
    if run.trace is None or not run.trace.devices or run.peaks is None:
        return None
    pallas_s, busy_s = run.trace.mean_pallas_s(), run.trace.mean_busy_s()
    time = "pallas" if pallas_s > 0 else "busy"
    print(f"[roofline] stencil3d time={time} pallas_s={pallas_s!r} "
          f"busy_s={busy_s!r}", file=sys.stderr, flush=True)
    if time == "pallas":
        return readers.kernel_roofline(run, "stencil3d")
    if busy_s <= 0:
        return None
    need = run.app.required_bytes(run.window)["stencil3d"]
    return 100.0 * need / (run.peaks["hbm_bytes_per_s"] * busy_s)
