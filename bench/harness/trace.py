"""Reduction of a ``jax.profiler`` trace to device time.

The trace is the ``.xplane.pb`` the profiler writes.  Each TPU is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per HLO
operation that ran, named by its HLO text
(``%name = <shape> <opcode>(<operands>), ...``).  Ops nest: a ``while``
event spans the ops of its body.  The host plane ``/host:CPU`` holds the
benchmark's own ``bench.*`` spans (``jax.profiler.TraceAnnotation``) on
the Python thread, beside the runtime's own host events.

Everything here is plain arithmetic on those events, so that every PR
reads the same number in the same way:

* busy time: the union of the intervals of a device's ops;
* kernel time: the summed durations of Pallas kernels, which lower to
  ``custom-call`` ops with ``custom_call_target="tpu_custom_call"``;
* collective time: the summed durations of collective ops;
* idle gaps: the holes in the busy union inside the traced window, each
  named by the innermost host event that was open at its middle.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

# Opcodes whose event spans other ops; left out of per-op tables.
CONTAINERS = frozenset({"while", "conditional", "call"})
# Collective ops, by the start of their opcode or instruction name (the
# latter catches a collective the compiler wrapped in a fusion of its own,
# such as ``%all-reduce-fusion``).
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
SYNC = "bench.sync"


@dataclasses.dataclass(frozen=True)
class Op:
    start: float   # ns, device clock
    end: float
    name: str      # HLO instruction name without its ``.N`` suffix
    opcode: str
    shape: str     # output shape, tiled layout stripped
    operands: int
    pallas: bool

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def collective(self) -> bool:
        return is_collective(self.name, self.opcode)


@dataclasses.dataclass(frozen=True)
class Span:
    start: float   # ns, host clock
    end: float
    name: str
    depth: int


def parse_hlo(text: str) -> tuple[str, str, str, int]:
    """``(name, opcode, shape, n_operands)`` of an HLO instruction's text;
    operands are counted for Pallas kernels only (0 for other ops)."""
    head, _, rest = text.partition(" = ")
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    if rest.startswith("("):
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode, _, args = rest.partition("(")
    shape = re.sub(r"\{[^{}]*\}", "", shape)
    if PALLAS_TARGET not in text:
        return name, opcode.strip(), shape, 0
    depth, n = 1, 0
    for ch in args:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                break
        elif ch == "%" and depth == 1:
            n += 1
    return name, opcode.strip(), shape, n


def is_collective(name: str, opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES) or name.startswith(COLLECTIVES)


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class Trace:
    devices: dict          # plane name -> list[Op]
    spans: list            # host events on the Python thread, as Span
    path: str = ""         # the .xplane.pb read

    @classmethod
    def load(cls, path: str) -> "Trace":
        """Read ``path``: an ``.xplane.pb`` or a directory holding one."""
        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                     recursive=True))
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        devices, spans, parsed = {}, [], {}
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                ops = []
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for ev in line.events:
                        if ev.name not in parsed:
                            parsed[ev.name] = parse_hlo(ev.name)
                        name, opcode, shape, n = parsed[ev.name]
                        ops.append(Op(ev.start_ns, ev.end_ns, name, opcode,
                                      shape, n, PALLAS_TARGET in ev.name))
                devices[plane.name] = sorted(ops, key=lambda o: o.start)
            elif plane.name == "/host:CPU":
                spans = _python_thread_spans(plane)
        return cls(devices=dict(sorted(devices.items(),
                                       key=lambda kv: _plane_id(kv[0]))),
                   spans=spans, path=path)

    # ------------------------------------------------------------------
    def window(self) -> Span | None:
        """The benchmark's traced window, on the host clock."""
        for s in self.spans:
            if s.name == WINDOW:
                return s
        return None

    def window_s(self) -> float:
        w = self.window()
        return 0.0 if w is None else (w.end - w.start) * 1e-9

    def busy_s(self, device: str) -> float:
        return length(union((o.start, o.end)
                            for o in self.devices[device])) * 1e-9

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.busy_s(d) for d in self.devices) / len(self.devices)

    def pallas_s(self, device: str) -> float:
        return sum(o.dur for o in self.devices[device] if o.pallas) * 1e-9

    def mean_pallas_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(self.pallas_s(d) for d in self.devices) / len(self.devices)

    def mean_collective_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(o.dur for ops in self.devices.values() for o in ops
                   if o.collective) * 1e-9 / len(self.devices)

    def pallas_calls(self, device: str) -> dict:
        """Pallas kernel events by ``(output shape, operands)``."""
        out: dict = {}
        for o in self.devices[device]:
            if o.pallas:
                k = (o.shape, o.operands)
                out[k] = out.get(k, 0) + 1
        return out

    # ------------------------------------------------------------------
    def top_ops(self, n: int = 10) -> list:
        """The ``n`` op groups that took most device time, averaged over
        the devices: ``[[label, seconds], ...]``."""
        agg: dict = {}
        for ops in self.devices.values():
            for o in ops:
                if o.opcode in CONTAINERS:
                    continue
                label = (f"pallas kernel {o.shape} ({o.operands} operands)"
                         if o.pallas else f"{o.opcode} {o.shape}")
                agg[label] = agg.get(label, 0.0) + o.dur
        nd = max(len(self.devices), 1)
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / nd] for k, v in top]

    def clock_offset(self, device: str) -> float:
        """Host minus device clock, from the end of the last op and the
        end of the host's last wait for it."""
        syncs = [s for s in self.spans if s.name == SYNC]
        ops = self.devices[device]
        if not syncs or not ops:
            return 0.0
        return max(s.end for s in syncs) - max(o.end for o in ops)

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest holes in device 0's busy union inside the
        window, each named by what the host was doing at its middle:
        ``[[label, seconds], ...]``."""
        w = self.window()
        if w is None or not self.devices:
            return []
        dev = next(iter(self.devices))
        off = self.clock_offset(dev)
        busy = union((o.start + off, o.end + off) for o in self.devices[dev])
        edges = [w.start] + [x for iv in busy for x in iv] + [w.end]
        gaps = []
        for s, e in zip(edges[0::2], edges[1::2]):
            s, e = max(s, w.start), min(e, w.end)
            if e > s:
                gaps.append((e - s, (s + e) / 2))
        gaps.sort(key=lambda g: -g[0])
        return [[self.host_label(mid), d * 1e-9] for d, mid in gaps[:n]]

    def host_label(self, t: float) -> str:
        """The ``bench.*`` span and the innermost host event open at
        ``t``, as ``outer > inner``."""
        open_ = [s for s in self.spans if s.start <= t <= s.end]
        if not open_:
            return "(no host event)"
        inner = max(open_, key=lambda s: s.depth)
        outer = [s for s in open_ if s.name.startswith("bench.")
                 and s.name != WINDOW]
        if outer:
            o = max(outer, key=lambda s: s.depth)
            if o is not inner:
                return f"{o.name} > {inner.name}"
        return inner.name


def _plane_id(name: str) -> int:
    m = re.search(r"(\d+)$", name)
    return int(m.group(1)) if m else 0


def _python_thread_spans(plane) -> list[Span]:
    """Host events of the thread that holds the ``bench.*`` spans, with
    their nesting depth."""
    for line in plane.lines:
        events = list(line.events)
        if not any(ev.name == WINDOW for ev in events):
            continue
        events.sort(key=lambda ev: (ev.start_ns, -ev.duration_ns))
        spans, stack = [], []
        for ev in events:
            while stack and stack[-1] <= ev.start_ns:
                stack.pop()
            spans.append(Span(ev.start_ns, ev.end_ns, ev.name, len(stack)))
            stack.append(ev.end_ns)
        return spans
    return []
