"""App adapter: ``Heat3D``, the paper's explicit 3-D heat diffusion step.

The window drives ``Heat3D``'s ``grid.parallel`` step back to back, as
``Heat3D.run`` calls it, from a field made on the devices from the seed.
Every ``CHUNK_S`` seconds of host time the host waits for the step it
queued one chunk back, so that the device always has work queued and the
host never runs more than two chunks ahead.  Two step pairs
``(T_{n-1}, T_n)`` at steps drawn from the seed among the first
``EARLY``, where a step still changes the field much, and the window's
last pair are kept and compared with the plain reference step.  A window
that ends before a drawn step leaves that pair to ``finish``, which steps
on from the window's last state after the window, untimed and untraced,
so a slow program is held to the same pairs as a fast one.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from harness import layout
from reference import heat3d as ref

SPANS = contextlib.nullcontext()
CHUNK_S = 0.2     # host time between waits for the step a chunk back
WARM_S = 2 * CHUNK_S  # the window's loop run in set-up, after the compile
EARLY = 256       # early pairs are drawn from steps 1 .. EARLY
EARLY_PAIRS = 2
REHEARSAL_EARLY = 4


class App:
    unit = "step"

    def __init__(self, cell, seed: int, rehearsal: bool):
        import jax
        import jax.numpy as jnp
        from repro.apps.heat3d import Heat3D

        cfg = dict(cell.config)
        if rehearsal:
            cfg.update(cfg["rehearsal"])
        self.cfg, self.mix = cfg, cell.mix
        self.local = (cfg["nx"], cfg["ny"], cfg["nz"])
        self.dims = tuple(cfg["dims"])
        self.dtype = jnp.dtype(cfg["dtype"])
        hide = self.mix["hide"]
        self.program = Heat3D(
            nx=cfg["nx"], ny=cfg["ny"], nz=cfg["nz"], lam=cfg["lam"],
            c0=cfg["c0"], lx=cfg["lx"],
            hide=None if hide is None else tuple(hide),
            use_kernel="interpret" if rehearsal else cfg["use_kernel"],
            dims=self.dims, dtype=self.dtype)
        self.gshape = layout.global_shape(self.local, self.dims)
        rng = np.random.default_rng(seed)
        self.T0, self.Ci = self._inputs(rng, jax, jnp)
        early = REHEARSAL_EARLY if rehearsal else EARLY
        self.sample_at = set(int(s) for s in rng.choice(
            np.arange(1, early + 1), EARLY_PAIRS, replace=False))
        self.kept: dict = {}
        self.last = 0     # the window's last step, read by step_error

    def _inputs(self, rng, jax, jnp):
        """``T0`` = 1.7 plus Gaussian bumps drawn from the seed; ``Ci`` =
        1 / c0 (the configuration's uniform heat capacity)."""
        nb = int(self.mix["bumps"])
        bumps = np.concatenate([
            rng.uniform(0.2, 0.8, (nb, 3)),
            rng.uniform(*self.mix["bump_width"], (nb, 1)),
            rng.uniform(*self.mix["bump_height"], (nb, 1))], axis=1)
        n = [m - 1 for m in self.gshape]
        ci = 1.0 / self.cfg["c0"]

        def field(k, ix, iy, iz, bumps):
            x, y, z = ix / n[0], iy / n[1], iz / n[2]
            if k == 1:
                return ci + 0.0 * (x + y + z)
            out = 1.7 + 0.0 * (x + y + z)
            for i in range(nb):
                cx, cy, cz, w, a = (bumps[i, j] for j in range(5))
                r2 = (x - cx) ** 2 + (y - cy) ** 2 + (z - cz) ** 2
                out = out + a * jnp.exp(-r2 / (2 * w * w))
            return out

        return layout.on_device(field, self.local, self.dims,
                                self.program.grid.sharding, self.dtype, 2,
                                args=(jnp.asarray(bumps, jnp.float32),))

    # ------------------------------------------------------------------
    def timed_step(self, T, Ci):
        """The call the window makes each step."""
        return self.program._step(T, Ci)

    def warmup(self):
        """Compile the step, then run the window's loop for ``WARM_S``, so
        that the window starts with the device's buffers in steady use."""
        self.timed_step(self.T0, self.Ci).block_until_ready()
        self.window(WARM_S)

    def window(self, seconds: float, span=None) -> dict:
        span = span or (lambda name: SPANS)
        T, Ci = self.T0, self.Ci
        kept, n, queued, synced = {}, 0, None, []
        t0 = time.perf_counter()
        deadline, wait_at = t0 + seconds, t0 + CHUNK_S
        while True:
            prev = T
            with span("bench.step"):
                T = self.timed_step(T, Ci)
            n += 1
            if n in self.sample_at:
                kept[n] = (prev, T)
            now = time.perf_counter()
            if now < wait_at:
                continue
            if queued is not None:
                with span("bench.sync"):
                    queued.block_until_ready()
                now = time.perf_counter()
                synced.append(now)
            queued = T
            if now >= deadline:
                break
            wait_at = now + CHUNK_S
        with span("bench.sync"):
            T.block_until_ready()
        elapsed = time.perf_counter() - t0
        kept[n] = (prev, T)
        self.kept, self.last = kept, n
        return {"units": n, "elapsed_s": elapsed, "failed": 0,
                "chunk_s": np.diff(synced).tolist()}

    def finish(self):
        """Keep the pairs at the drawn steps the window did not reach: step
        on with the window's own call from its last state.  None of these
        steps is counted, timed or traced."""
        T = self.kept[self.last][1]
        for n in range(self.last + 1, max(self.sample_at) + 1):
            prev, T = T, self.timed_step(T, self.Ci)
            if n in self.sample_at:
                self.kept[n] = (prev, T)
        T.block_until_ready()

    def release(self):
        """Fetch the kept pairs to the host and drop every device array."""
        self.kept = {n: tuple(layout.dedup(a, self.local, self.dims)
                              for a in pair) for n, pair in self.kept.items()}
        self.T0 = self.Ci = self.program = None

    def check(self) -> tuple[dict, dict]:
        """``({"increment_error": worst early pair, "step_error": the
        window's last pair}, per-pair readings)``.  An early pair never
        reached (no ``finish``) reads NaN, which no limit passes."""
        coef = ref.coefficients(self.cfg, self.gshape)
        last = self.last
        each = {}
        for n, (prev, T) in sorted(self.kept.items()):
            if n in self.sample_at:
                each[f"increment_error.{n}"] = ref.increment_error(prev, T, coef)
        each[f"step_error.{last}"] = ref.step_error(*self.kept[last], coef)
        early = [v for k, v in each.items() if k.startswith("increment")]
        worst = (float("nan") if len(early) < EARLY_PAIRS
                 else max(early, key=lambda v: (v != v, v)))
        return {"increment_error": worst,
                "step_error": each[f"step_error.{last}"]}, each

    # ------------------------------------------------------------------
    def info(self) -> dict:
        return {"hide": self.program._hide_widths,
                "local": self.local, "dims": self.dims,
                "global": self.gshape}

    def required_bytes(self, win: dict) -> dict:
        """Bytes one chip must move for the window's steps: ``T`` read,
        ``Ci`` read and ``T`` written once per step, at the local size."""
        cells = int(np.prod(self.local))
        per_step = 3 * cells * self.dtype.itemsize
        return {"stencil3d": per_step * win["units"]}

    def t_eff_gb_s(self, win: dict) -> float:
        """The paper's T_eff of one chip: A_eff = (2 D_u + D_k) * cells *
        itemsize per step (one unknown field, one known, the local cells)
        over the time per step."""
        cells = int(np.prod(self.local))
        a_eff = (2 * 1 + 1) * cells * self.dtype.itemsize
        return a_eff / (win["elapsed_s"] / win["units"]) / 1e9
