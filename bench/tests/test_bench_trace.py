"""The trace reduction, on a small trace recorded on a TPU v5e chip
(``data/heat3d-256.hide.xplane.pb``: 16 hidden heat steps at 256^3, made
by ``tools/record_trace.py``)."""

import os

import pytest

from harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURE = os.path.join(DATA, "heat3d-256.hide.xplane.pb")
STEPS = 16
# One hidden step launches the heat kernel on two slabs per axis and on
# the interior: local 256^3 with hide widths (16, 2, 2).
SLABS = {"f32[18,256,256]": 2, "f32[256,4,256]": 2, "f32[256,256,4]": 2,
         "f32[224,252,252]": 1}


@pytest.fixture(scope="module")
def trace():
    return tr.Trace.load(FIXTURE)


@pytest.fixture(scope="module")
def raw_ops():
    """(start, end, name) of every XLA op of device 0, read with nothing
    but the profiler's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(FIXTURE)
    plane = next(p for p in data.planes if p.name == "/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    return [(e.start_ns, e.end_ns, e.name) for e in line.events]


def test_busy_union_matches_hand_merged_intervals(trace, raw_ops):
    busy, end = 0.0, None
    for s, e, _ in sorted(raw_ops):
        if end is None or s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    assert trace.busy_s("/device:TPU:0") == pytest.approx(busy * 1e-9,
                                                          rel=1e-12)
    assert 0 < trace.mean_busy_s() <= trace.window_s()


def test_kernel_events_are_found_by_name(trace, raw_ops):
    calls = trace.pallas_calls("/device:TPU:0")
    assert calls == {(shape, 5): n * STEPS for shape, n in SLABS.items()}
    want = sum(e - s for s, e, name in raw_ops if "tpu_custom_call" in name)
    assert trace.pallas_s("/device:TPU:0") == pytest.approx(want * 1e-9)
    assert trace.mean_collective_s() == 0.0


def test_window_gaps_and_top_ops(trace):
    assert trace.window() is not None
    gaps = trace.idle_gaps(10)
    assert 0 < len(gaps) <= 10
    assert all(g[1] >= 0 for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    top = trace.top_ops(10)
    assert len(top) == 10 and any(k.startswith("pallas kernel") for k, _ in top)
    assert sum(v for _, v in top) <= trace.mean_busy_s() * (1 + 1e-9)


def test_union_and_hlo_parsing():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.length(tr.union([(0, 2), (1, 3)])) == 3
    text = ('%fusion.3 = (f32[4,4]{1,0:T(8,128)}, f32[4]{0}) fusion('
            'f32[4,4]{1,0} %a, f32[4]{0} %b), kind=kLoop')
    assert tr.parse_hlo(text) == ("fusion", "fusion", "(f32[4,4], f32[4])", 0)
    text = ('%body.56 = f32[130,130,130]{2,1,0:T(8,128)S(1)} custom-call('
            'f32[130,130,130]{2,1,0:T(8,128)S(1)} %u, f32[130,130,130]{2,1,0}'
            ' %c), custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={f32[130,130,130]{2,1,0}}')
    assert tr.parse_hlo(text) == ("body", "custom-call", "f32[130,130,130]", 2)
    text = '%all-reduce.1 = f32[] all-reduce(f32[] %x), to_apply=%sum'
    assert tr.is_collective(*tr.parse_hlo(text)[:2])
    text = ('%collective-permute-start.2 = (f32[4,130], f32[4,130]) '
            'collective-permute-start(f32[4,130] %s), channel_id=3')
    assert tr.is_collective(*tr.parse_hlo(text)[:2])
    assert not tr.is_collective(*tr.parse_hlo(
        '%fusion.7 = f32[4,4] fusion(f32[4,4] %a), kind=kLoop')[:2])
