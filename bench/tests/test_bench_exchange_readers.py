"""The exchange layer's readers.  ``collective_exposed_ms.heat`` on
synthetic two-device traces, where the time a collective runs alone is
known exactly; ``exchange_ms.heat`` on the scoped one-chip fixture
(``data/heat3d-256.hide.scoped.*``), where nothing exchanges."""

import os
import types

import pytest

from harness import spec
from harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "heat3d-256.hide.scoped.xplane.pb")
HLO = os.path.join(DATA, "heat3d-256.hide.scoped.hlo.txt")
EXPOSED = spec.load_module("metrics", "collective_exposed_ms.heat")
EXCHANGE = spec.load_module("metrics", "exchange_ms.heat")


def _op(start, end, opcode="fusion"):
    return tr.Op(start, end, opcode, opcode, "f32[256,256]", 0, False)


def _permute(start, end):
    return _op(start, end, "collective-permute-done")


def _run(devices, units=1, text=None):
    return types.SimpleNamespace(trace=tr.Trace(devices=devices, spans=[]),
                                 hlo_text=text, app=None,
                                 window={"units": units})


# Device 0 computes over [0, 1000) ns; device 1 runs a collective alone.
# (collective on device 0, ns it runs alone there)
CASES = {"covered": ((200, 800), 0),
         "partly covered": ((900, 1500), 500),
         "uncovered": ((2000, 2300), 300)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_collective_counts_only_where_no_other_op_runs(case):
    (s, e), alone = CASES[case]
    devices = {"/device:TPU:0": [_op(0, 1000), _permute(s, e)],
               "/device:TPU:1": [_permute(0, 400)]}
    got = EXPOSED.read(_run(devices, units=2))
    # ns over two devices and two steps, in ms.
    assert got == pytest.approx((alone + 400) / 2 / 2 * 1e-6, rel=1e-12)


def test_overlapping_collectives_count_once_and_containers_cover_nothing():
    devices = {"/device:TPU:0": [_op(0, 3000, "while"), _op(0, 100),
                                 _permute(50, 400), _permute(300, 600),
                                 _op(500, 550, "all-reduce")],
               "/device:TPU:1": [_op(0, 600)]}
    # Device 0: collectives cover [50, 600), of which [50, 100) lies under
    # the fusion: 500 ns alone; the while spans everything and covers
    # nothing.
    assert EXPOSED.read(_run(devices)) == pytest.approx(
        500 / 2 * 1e-6, rel=1e-12)


def test_untraced_runs_read_none():
    assert EXPOSED.read(types.SimpleNamespace(trace=None)) is None
    assert EXPOSED.read(_run({})) is None
    assert EXCHANGE.read(types.SimpleNamespace(trace=None)) is None


def test_exchange_reads_zero_where_nothing_exchanges():
    """The fixture's one chip has no neighbour: its step joins with the
    module, and no op falls under ``halo.update``, so the reader gives 0
    (a measured absence), not None (nothing to read)."""
    with open(HLO) as f:
        text = f.read()
    trace = tr.Trace.load(FIXTURE)
    assert not any(o.collective for ops in trace.devices.values()
                   for o in ops)
    assert EXCHANGE.read(types.SimpleNamespace(
        trace=trace, hlo_text=text, app=None, window={"units": 1})) == 0.0
    assert EXPOSED.read(types.SimpleNamespace(
        trace=trace, window={"units": 1})) == 0.0
