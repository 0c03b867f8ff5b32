"""Arithmetic shared by the metric readers under ``bench/metrics``.

Each reader returns None where its run has nothing to read (no trace, no
device plane, no such event), and the harness then leaves the metric out.
"""

from __future__ import annotations


def _traced(run) -> bool:
    return run.trace is not None and bool(run.trace.devices)


def kernel_roofline(run, family: str):
    """Share (%) of the HBM roofline: the bytes the algorithm needs one
    chip's ``family`` kernels to move, over the peak bandwidth times those
    kernels' device time."""
    if not _traced(run) or run.peaks is None:
        return None
    t = run.trace.mean_pallas_s()
    if t <= 0:
        return None
    need = run.app.required_bytes(run.window)[family]
    return 100.0 * need / (run.peaks["hbm_bytes_per_s"] * t)


def idle_share(run):
    """Share (%) of the traced window in which no op ran on the device,
    averaged over the chips."""
    if not _traced(run):
        return None
    w = run.trace.window_s()
    return None if w <= 0 else 100.0 * (1.0 - run.trace.mean_busy_s() / w)


def outside_kernels_ms(run):
    """Device time per unit (ms) in ops other than the Pallas kernels."""
    if not _traced(run):
        return None
    t = run.trace.mean_busy_s() - run.trace.mean_pallas_s()
    return 1e3 * t / run.window["units"]


def per_unit_ms(run):
    """The whole window over the units completed (ms), host clock."""
    return 1e3 * run.window["elapsed_s"] / run.window["units"]
