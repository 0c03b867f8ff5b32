"""Smoke run of the main path on a TPU: the heat stencil and the mgcg
Poisson solve, through ``init_global_grid`` and the ``repro.apps``
entry points, with every fused kernel compiled for the chip.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --chips 4        # a four-chip host, dims=(2,2,1)
    python chip_smoke.py --cpu-rehearsal [--chips 4]
                                          # tiny sizes on the CPU with the
                                          # kernels in interpret mode

Phases (one chip):

* heat: ``Heat3D`` at 256^3 f32 with the paper's ``hide=(16, 2, 2)`` and
  with ``hide=None``; a warm-up step, then 10 timed steps from a Gaussian
  field, compared with the NumPy oracle of the same 10 steps.
* poisson: ``Poisson3D`` at 130^3 f32 (7 multigrid levels, down to 4^3
  local), ``solve("mgcg", tol=1e-5)``, compared with the NumPy CG oracle.

With ``--chips 4`` only the sharded paths run: the hidden heat step at
256^3 local on ``dims=(2, 2, 1)`` against the oracle of the global grid,
and the mgcg solve at 130^3 local against the same global problem solved
on one device.  Each field must hold its shards on four distinct devices.

The printed times are smoke numbers (one run, compile included where
labelled), not benchmark metrics.  Every phase must pass; the script exits
non-zero on the first failure and when the platform is not a TPU, and
only then prints, as its last line,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The rehearsal never prints ``"ok"``.

Compile cache: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
itself); otherwise ``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# Local sizes (including the halo ring): the chip sizes, and the tiny
# rehearsal sizes that keep interpret mode fast on the CPU.
SIZES = {"chip": {"heat": 256, "poisson": 130},
         "rehearsal": {"heat": 34, "poisson": 18}}
NT = 10
HEAT_ATOL = 1e-5   # f32 against the f64 oracle; one step moves ~1e-3
POISSON_TOL = 1e-5  # mgcg relative residual
POISSON_RTOL = 1e-5  # max |u - u_ref| / max |u_ref|; 2.3e-7 on XLA:CPU at 130^3


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, kernels in interpret mode; "
                         "never reports ok")
    return ap.parse_args(argv)


ARGS = parse_args()
if ARGS.cpu_rehearsal:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count="
                               f"{ARGS.chips}")

sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))

from repro import telemetry as tele  # noqa: E402
from repro.apps.heat3d import Heat3D  # noqa: E402
from repro.apps.poisson import Poisson3D  # noqa: E402
from repro.core import make_grid_mesh  # noqa: E402
from repro.kernels import dispatch  # noqa: E402

CACHE = {"hits": 0, "misses": 0}


def _count_cache(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        CACHE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        CACHE["misses"] += 1


def say(phase, **kv):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class PhaseFailed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseFailed(msg)


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def check_resolved(phase, records, want):
    """Every dispatch site must have resolved to ``want``."""
    seen = sorted(set(records))
    for where, shape, impl, bx in seen:
        say(phase, site=where, shape="x".join(map(str, shape)), impl=impl,
            bx=bx)
    check(seen, f"{phase}: no kernel dispatch site was traced")
    bad = [r for r in seen if r[2] != want]
    check(not bad, f"{phase}: dispatch sites not on {want!r}: {bad}")


def shard_devices(phase, name, arr, want):
    devs = sorted({s.device.id for s in arr.addressable_shards})
    say(phase, field=name, shard_devices=devs)
    check(len(devs) == want,
          f"{phase}: {name} is held by devices {devs}, expected {want}")


def gaussian(grid):
    """1.7 + a Gaussian bump in the middle of the global grid."""
    n = grid.global_shape

    def fn(ix, iy, iz):
        r2 = sum(((i / (m - 1)) - 0.5) ** 2 for i, m in zip((ix, iy, iz), n))
        return 1.7 + jnp.exp(-r2 / 0.02)

    return grid.from_global_fn(fn)


def heat_phase(phase, n, impl, hide, dims, ndev):
    app = Heat3D(nx=n, ny=n, nz=n, dtype=jnp.float32, use_kernel=impl,
                 hide=hide, dims=dims)
    g = app.grid
    T0, Ci = gaussian(g), g.full(1.0 / app.c0)
    t0 = time.perf_counter()
    with dispatch.recording() as rec:
        app.run(1, T0, Ci)
    warm_s = time.perf_counter() - t0
    check_resolved(phase, rec, impl)
    t0 = time.perf_counter()
    T, _ = app.run(NT, T0, Ci)
    wall_s = time.perf_counter() - t0
    shard_devices(phase, "T", T, ndev)
    G0 = g.gather(T0)
    ref = app.oracle(NT, G0)
    err = float(np.abs(g.gather(T) - ref).max())
    moved = float(np.abs(ref - G0).max())
    say(phase, smoke="not a benchmark", local=f"{n}^3", dims=g.dims,
        global_shape=g.global_shape, hide=app._hide_widths)
    say(phase, first_call_s=f"{warm_s:.3f}",
        compile_s_est=f"{warm_s - wall_s / NT:.3f}",
        steps=NT, wall_s=f"{wall_s:.4f}", s_per_step=f"{wall_s / NT:.6f}")
    say(phase, max_abs_err=f"{err:.3e}", tol=HEAT_ATOL,
        oracle_change=f"{moved:.3e}", peak_bytes_in_use=peak_bytes())
    check(np.isfinite(err) and err <= HEAT_ATOL,
          f"{phase}: max error {err:.3e} > {HEAT_ATOL}")
    check(moved > 100 * HEAT_ATOL,
          f"{phase}: the oracle barely moved ({moved:.3e}); no real test")


def poisson_solve(phase, app, impl):
    t0 = time.perf_counter()
    with dispatch.recording() as rec:
        u, info = app.solve("mgcg", tol=POISSON_TOL)
        u.block_until_ready()
    first_s = time.perf_counter() - t0
    check_resolved(phase, rec, impl)
    t0 = time.perf_counter()
    u, info = app.solve("mgcg", tol=POISSON_TOL)
    u.block_until_ready()
    wall_s = time.perf_counter() - t0
    levels = len(app.grid.hierarchy())
    say(phase, smoke="not a benchmark", local=app.grid.local_shape,
        dims=app.grid.dims, global_shape=app.grid.global_shape,
        levels=levels, status=info.status.name,
        iterations=info.iterations, relres=f"{float(info.relres):.3e}",
        tol=POISSON_TOL)
    say(phase, first_call_s=f"{first_s:.3f}",
        compile_s_est=f"{first_s - wall_s:.3f}", wall_s=f"{wall_s:.4f}",
        peak_bytes_in_use=peak_bytes())
    check(info.status == tele.SolveStatus.CONVERGED,
          f"{phase}: status {info.status.name}")
    check(float(info.relres) <= POISSON_TOL,
          f"{phase}: relres {float(info.relres):.3e} > {POISSON_TOL}")
    return u


def compare(phase, got, ref, what):
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    say(phase, reference=what, max_rel_err=f"{err:.3e}", tol=POISSON_RTOL)
    check(np.isfinite(err) and err <= POISSON_RTOL,
          f"{phase}: max relative error {err:.3e} > {POISSON_RTOL}")


def poisson_phase(phase, n, impl):
    app = Poisson3D(nx=n, ny=n, nz=n, dtype=jnp.float32, use_kernel=impl)
    u = poisson_solve(phase, app, impl)
    t0 = time.perf_counter()
    ref = app.oracle(tol=1e-8)
    say(phase, oracle_s=f"{time.perf_counter() - t0:.1f}")
    compare(phase, app.grid.gather(u), ref, "numpy CG (f64, tol 1e-8)")


def poisson_sharded_phase(phase, n, impl, ndev):
    app = Poisson3D(nx=n, ny=n, nz=n, dims=(2, 2, 1), dtype=jnp.float32,
                    use_kernel=impl)
    u = poisson_solve(phase, app, impl)
    shard_devices(phase, "u", u, ndev)
    # The same global problem on one device of this host.
    g = app.grid
    one = Poisson3D(nx=g.nx_g(), ny=g.ny_g(), nz=g.nz_g(), dtype=jnp.float32,
                    use_kernel=impl, mesh=make_grid_mesh(
                        3, dims=(1, 1, 1), devices=jax.devices()[:1]))
    check(one.grid.global_shape == g.global_shape, "global shapes differ")
    u1 = poisson_solve(phase + ".one_device", one, impl)
    compare(phase, g.gather(u), one.grid.gather(u1),
            "the same global problem solved on one device")


def main():
    jax.monitoring.register_event_listener(_count_cache)
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print("device", json.dumps(dev), flush=True)
    print("compile_cache_dir", jax.config.jax_compilation_cache_dir,
          flush=True)
    rehearsal = ARGS.cpu_rehearsal
    if not rehearsal and dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (platform {dev['platform']!r}); "
              "use --cpu-rehearsal for the CPU path", file=sys.stderr)
        return 1
    if dev["count"] != ARGS.chips:
        print(f"chip_smoke: --chips {ARGS.chips} needs {ARGS.chips} "
              f"device(s), found {dev['count']}", file=sys.stderr)
        return 1
    sizes = SIZES["rehearsal" if rehearsal else "chip"]
    impl = "interpret" if rehearsal else "pallas"
    try:
        if ARGS.chips == 1:
            heat_phase("heat.hide", sizes["heat"], impl, (16, 2, 2), None, 1)
            heat_phase("heat.nohide", sizes["heat"], impl, None, None, 1)
            poisson_phase("poisson", sizes["poisson"], impl)
        else:
            heat_phase("heat.4chip", sizes["heat"], impl, (16, 2, 2),
                       (2, 2, 1), 4)
            poisson_sharded_phase("poisson.4chip", sizes["poisson"], impl, 4)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print("compile_cache", json.dumps(CACHE), flush=True)
    if rehearsal:
        print(json.dumps({"rehearsal": "passed", "device": dev}))
        return 0
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
