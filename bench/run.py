"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload <config>.<traffic> --seed <n> \\
        --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <cell> --seed <n> --seconds 1 --rehearsal

A run is one process, the only one that touches JAX.  It builds the
cell's app from its configuration and its traffic mix, makes the inputs on
the devices from ``--seed``, warms up the cell's own shapes (set-up, timed
from the start of the process), then drives the app for ``--seconds``
seconds.  ``--trace 1`` instead takes a profiler trace of a steady stretch
and reads the per-layer metrics from it.  Once the window has closed and
the device's peak memory has been read, the app finishes the answers it
compares (``finish``: steps the window did not reach, untimed); once the
program's state is freed, they are compared with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and, last, ``checks``: each number compared with its
limit.  The checks are also the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run exits 1 and
prints no result.

``--rehearsal`` runs the cell at the tiny sizes its configuration gives,
on CPU devices with the kernels in interpret mode, and prints no device
metric.

Compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when set, otherwise
``.jax_cache/`` at the root of the checkout.
"""

from __future__ import annotations

import time

START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "traces",
    "/jax/core/compile/backend_compile_duration": "compiles",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
TRACE_S = 1.0   # the longest stretch a --trace 1 run traces


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="cell: <config>.<traffic>")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on CPU devices, kernels in interpret "
                         "mode; no device metric")
    return ap.parse_args(argv)


def say(tag, **kv):
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class GcPauses:
    """Collections of Python's garbage collector, and their pauses, while
    ``on``: a host pause the device's queue does not cover shows as idle
    device time."""

    def __init__(self):
        self.on, self.pauses, self._t = False, [], 0.0
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))


class CompileCounter:
    """Traces, compilations and cache reads, counted while ``on``."""

    def __init__(self, jax):
        self.on = False
        self.counts = dict.fromkeys(
            list(COMPILE_EVENTS.values()) + list(CACHE_EVENTS.values()), 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if self.on and event in COMPILE_EVENTS:
            self.counts[COMPILE_EVENTS[event]] += 1

    def _event(self, event, **_):
        if self.on and event in CACHE_EVENTS:
            self.counts[CACHE_EVENTS[event]] += 1


@dataclasses.dataclass
class Run:
    """What a metric reader reads: ``bench/metrics/<name>.py``'s
    ``read(run)`` returns a number, or None where it finds nothing."""
    cell: spec.Cell
    app: object
    setup_s: float
    window: dict
    trace: object = None     # harness.trace.Trace of a --trace 1 run
    peaks: dict | None = None


def configure_jax(chips: int, rehearsal: bool):
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    if rehearsal:
        jax.config.update("jax_enable_compilation_cache", False)
        return jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache:
        cache = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
    os.makedirs(cache, exist_ok=True)
    # Cache every program, so that a second run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def traced_window(jax, app, seconds: float, keep: str | None = None):
    """The app's window for ``seconds`` under the profiler; returns the
    window's record and the reduced trace.  ``keep`` is a path to copy
    the raw ``.xplane.pb`` to."""
    from harness.trace import Trace, WINDOW

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                win = app.window(seconds, span=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        trace = Trace.load(tmp)
        if keep:
            shutil.copy(trace.path, keep)
        return win, trace
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def read_metrics(entries, run: Run) -> dict:
    out = {}
    for m in entries:
        value = spec.load_module("metrics", m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.find_cell(args.workload)
    jax = configure_jax(cell.chips, args.rehearsal)
    counter = CompileCounter(jax)
    gcp = GcPauses()
    devices = jax.devices()
    t_backend = time.monotonic() - START
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say("device", **device)
    peaks = None
    if not args.rehearsal:
        if device["platform"] != "tpu":
            print(f"bench: no TPU (platform {device['platform']!r}); "
                  "--rehearsal runs the CPU path", file=sys.stderr)
            return 1
        peaks = spec.peaks(device["kind"])
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chip(s), found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    used = devices[:cell.chips]

    from repro.kernels import dispatch

    App = spec.load_module("apps", cell.app).App
    counter.on = True
    with dispatch.recording() as records:
        app = App(cell, args.seed, args.rehearsal)
        t_build = time.monotonic() - START
        app.warmup()
    counter.on = False
    # Set-up's objects live to the end of the run: move them out of the
    # collector's reach, so that a collection in the window scans only
    # what the window made.
    gc.collect()
    gc.freeze()
    setup_s = time.monotonic() - START
    sites = sorted(set(records))
    for where, shape, impl, bx in sites:
        say("dispatch", site=where, shape="x".join(map(str, shape)),
            impl=impl, bx=bx)
    say("setup", backend_s=t_backend, built_s=t_build, setup_s=setup_s,
        fallbacks_to_ref=sum(1 for s in sites if s[2] == "ref"),
        **app.info(), **{f"setup_{k}": v for k, v in counter.counts.items()})

    counter.counts = dict.fromkeys(counter.counts, 0)
    counter.on = gcp.on = True
    trace = None
    if args.trace:
        win, trace = traced_window(jax, app, min(args.seconds, TRACE_S))
    else:
        win = app.window(args.seconds)
    counter.on = gcp.on = False
    say("window", units=win["units"], unit=app.unit,
        elapsed_s=win["elapsed_s"], failed=win["failed"],
        **{f"window_{k}": v for k, v in counter.counts.items()})
    chunks = sorted(win.get("chunk_s") or [])
    if chunks:
        say("window", chunks=len(chunks), chunk_ms_min=1e3 * chunks[0],
            chunk_ms_median=1e3 * chunks[len(chunks) // 2],
            chunk_ms_max=1e3 * chunks[-1],
            slow_chunks=sum(c > 1.5 * chunks[len(chunks) // 2]
                            for c in chunks))
    say("window", gc_collections=len(gcp.pauses),
        gc_full=sum(1 for g, _ in gcp.pauses if g == 2),
        gc_pause_ms_max=1e3 * max((p for _, p in gcp.pauses), default=0.0),
        gc_pause_ms_total=1e3 * sum(p for _, p in gcp.pauses))
    if hasattr(app, "t_eff_gb_s"):
        say("window", t_eff_gb_s=app.t_eff_gb_s(win))
    device["memory_peak_bytes"] = memory_peak(used)

    run = Run(cell=cell, app=app, setup_s=setup_s, window=win,
              trace=trace, peaks=peaks)
    entries = cell.per_layer if args.trace else cell.end_to_end
    metrics = read_metrics(entries, run)

    app.finish()
    app.release()
    compared, readings = app.check()
    for k, v in readings.items():
        say("reading", **{k: v})
    limits = cell.config["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in compared.items()}
    over = [k for k, c in checks.items() if not c["value"] <= c["limit"]]
    failed = win["failed"] + sum(1 for k, v in readings.items()
                                 if not v <= limits[k.split(".")[0]])
    correct = not over and failed == 0 and win["units"] > 0

    result = {"correct": correct, "attempted": win["units"], "failed": failed}
    if args.rehearsal:
        result["rehearsal"] = True
        result["would_report"] = sorted(m["name"] for m in entries)
    else:
        if trace is not None:
            device["busy_s"] = trace.mean_busy_s()
            device["window_s"] = trace.window_s()
            result["breakdown"] = {"device_ops": trace.top_ops(10),
                                   "idle_gaps": trace.idle_gaps(10)}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
