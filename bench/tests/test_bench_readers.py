"""``stencil3d_roofline`` reads the same work whatever implements it:
against the Pallas kernels' device time where the step runs them (as on
the scoped hide fixture, ``data/heat3d-256.hide.scoped.xplane.pb``), and
against the device's busy time where it runs none (the XLA reference
spelling), and says on standard error which time it divided by."""

import os
import types

import pytest

from harness import spec
from harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "heat3d-256.hide.scoped.xplane.pb")
PEAKS = spec.peaks("TPU v5 lite")
BW = PEAKS["hbm_bytes_per_s"]
ROOFLINE = spec.load_module("metrics", "stencil3d_roofline")


def _run(trace, per_unit: float, units: int):
    app = types.SimpleNamespace(
        required_bytes=lambda win: {"stencil3d": per_unit * win["units"]})
    return types.SimpleNamespace(trace=trace, peaks=PEAKS, app=app,
                                 window={"units": units})


def _op(start, end):
    return tr.Op(start, end, "fusion", "fusion", "f32[512,512,512]", 0, False)


def test_a_step_without_pallas_kernels_reads_against_busy_time(capsys):
    # Two overlapping fusions and one apart: busy 3 ms + 1 ms, in ns.
    ops = [_op(0, 2e6), _op(1e6, 3e6), _op(5e6, 6e6)]
    trace = tr.Trace(devices={"/device:TPU:0": ops}, spans=[])
    assert trace.mean_pallas_s() == 0
    got = ROOFLINE.read(_run(trace, per_unit=1e9, units=2))
    assert got == pytest.approx(100 * 2e9 / (BW * 4e-3), rel=1e-12)
    assert "[roofline] stencil3d time=busy pallas_s=0" in capsys.readouterr().err


def test_the_scoped_fixture_reads_the_pallas_kernels_alone(capsys):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(SCOPED)
    dev = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in dev.lines if ln.name == "XLA Ops")
    kernels_s = 1e-9 * sum(e.duration_ns for e in line.events
                           if tr.PALLAS_TARGET in e.name)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    steps = sum(1 for ln in host.lines for e in ln.events
                if e.name == "bench.step")
    trace = tr.Trace.load(SCOPED)
    assert 0 < kernels_s < trace.mean_busy_s()
    per_unit = 3 * 256 ** 3 * 4
    got = ROOFLINE.read(_run(trace, per_unit, steps))
    assert got == pytest.approx(100 * per_unit * steps / (BW * kernels_s),
                                rel=1e-12)
    assert "[roofline] stencil3d time=pallas" in capsys.readouterr().err


def test_untraced_runs_read_nothing():
    assert ROOFLINE.read(_run(None, 1e9, 1)) is None
    empty = tr.Trace(devices={}, spans=[])
    assert ROOFLINE.read(_run(empty, 1e9, 1)) is None
