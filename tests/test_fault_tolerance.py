"""Fault-tolerance paths: NaN guard, straggler watchdog, elastic resume
(checkpoint taken on one mesh, resumed on a different mesh layout), and
the solver-side failure story: a NaN-poisoned two-rank solve must exit
early with ``DIVERGED_NONFINITE``, leave one flight-record JSONL per
rank behind, merge into a Perfetto trace via the diag CLI, and resume
cleanly from a checkpoint taken before the failure."""

import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp

from _mp import run as mp_run


def _toy_setup():
    import dataclasses
    import importlib

    from repro import optim
    from repro.data import SyntheticLMData
    from repro.models import params as pm, transformer as tf
    from repro.train import TrainCfg, Trainer, make_train_step

    cfg = importlib.import_module("repro.configs.llama3_2_1b").SMOKE
    cfg = dataclasses.replace(cfg, dtype="float32")
    tcfg = TrainCfg(opt=optim.AdamWCfg(lr=1e-3), warmup=2, total_steps=50)
    params = pm.materialize(tf.param_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    opt = optim.init(params, tcfg.opt)
    step = jax.jit(make_train_step(cfg, tcfg))
    data = SyntheticLMData(vocab=cfg.vocab, batch=4, seq=16, seed=0)
    return cfg, params, opt, step, data


def test_nan_guard_skips_update():
    from repro.train import Trainer

    cfg, params, opt, step, data = _toy_setup()
    calls = {"n": 0}

    def poisoned_step(p, o, b):
        calls["n"] += 1
        np_, no_, m = step(p, o, b)
        if calls["n"] == 3:  # poison one step
            m = dict(m, loss=jnp.asarray(float("nan")))
        return np_, no_, m

    tr = Trainer(cfg=cfg, train_step=poisoned_step, data=data,
                 ckpt_dir=None, log_every=100, max_bad_steps=5)
    p2, o2, hist = tr.run(params, opt, 6)
    assert len(hist) == 5  # the poisoned step is excluded from history
    assert all(np.isfinite(hist))
    assert tr.bad_steps == 0  # guard reset after a good step


def test_watchdog_flags_straggler():
    from repro.train import Trainer

    cfg, params, opt, step, data = _toy_setup()
    calls = {"n": 0}

    def slow_step(p, o, b):
        calls["n"] += 1
        out = step(p, o, b)
        jax.block_until_ready(out[2]["loss"])
        if calls["n"] == 6:
            time.sleep(1.5)  # inject a straggler step
        return out

    tr = Trainer(cfg=cfg, train_step=slow_step, data=data,
                 ckpt_dir=None, log_every=100, straggler_factor=2.0)
    tr.run(params, opt, 8)
    assert tr.straggler_events >= 1


def test_elastic_resume_across_meshes():
    """Checkpoint on a (4,2) mesh, resume on (2,4) — state re-shards and
    training continues bit-compatibly with an unsharded run."""
    mp_run(
        """
import dataclasses, importlib, tempfile
from repro import ckpt, optim
from repro.data import SyntheticLMData
from repro.distributed.sharding import axis_rules, default_rules
from repro.models import params as pm, transformer as tf
from repro.train import TrainCfg, make_train_step

cfg = importlib.import_module("repro.configs.llama3_2_1b").SMOKE
cfg = dataclasses.replace(cfg, dtype="float32")
tcfg = TrainCfg(opt=optim.AdamWCfg(lr=1e-3), warmup=2, total_steps=50)
data = SyntheticLMData(vocab=cfg.vocab, batch=8, seq=16, seed=0)
specs = tf.param_specs(cfg)
params0 = pm.materialize(specs, jax.random.PRNGKey(0), jnp.float32)
opt0 = optim.init(params0, tcfg.opt)
base = make_train_step(cfg, tcfg)

def run_steps(params, opt, steps, rules, start=0):
    def fn(p, o, b):
        with axis_rules(rules):
            return base(p, o, b)
    stepf = jax.jit(fn)
    for s in range(start, start + steps):
        params, opt, m = stepf(params, opt, data.batch_at(jnp.asarray(s)))
    return params, opt, float(m["loss"])

# reference: 4 steps, no sharding
pr, orr, loss_ref = run_steps(params0, opt0, 4, None)

# mesh A: 2 steps, checkpoint
meshA = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
rulesA = default_rules(meshA, batch_size=8)
pA = jax.tree.map(jax.device_put, params0, pm.shardings(specs, rulesA))
p1, o1, _ = run_steps(pA, opt0, 2, rulesA)
with tempfile.TemporaryDirectory() as d:
    ckpt.save({"params": p1, "opt": o1}, 2, d)

    # mesh B (elastic change): restore with B shardings, run 2 more
    meshB = jax.make_mesh((2, 4), ("data", "model"),
                      axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rulesB = default_rules(meshB, batch_size=8)
    shardB = {"params": pm.shardings(specs, rulesB),
              "opt": optim.state_shardings(specs, tcfg.opt, rulesB)}
    state = ckpt.restore({"params": p1, "opt": o1}, 2, d, shardings=shardB)
    p2, o2, loss_b = run_steps(state["params"], state["opt"], 2, rulesB, start=2)

# the elastic run must match the unsharded reference closely
assert abs(loss_b - loss_ref) / abs(loss_ref) < 2e-4, (loss_b, loss_ref)
print("OK elastic resume", loss_b, loss_ref)
""",
        ndev=8,
        timeout=1200,
    )


def test_nan_solve_flight_records_diag_and_resume():
    """Two-rank CG with a NaN-poisoned coefficient: early exit with
    DIVERGED_NONFINITE, one flight-record JSONL per rank, diag-CLI merge
    into a Perfetto trace + imbalance report, and a clean checkpoint
    resume afterwards."""
    out = mp_run(
        """
import glob, io, json, os, tempfile
import contextlib as cl
jax.config.update("jax_enable_x64", True)
from repro import ckpt, telemetry as tele
from repro.apps.poisson import Poisson3D
from repro.telemetry import diag

out = tempfile.mkdtemp()
fdir = os.path.join(out, "flight")
app = Poisson3D(nx=10, ny=10, nz=10, dims=(2, 1, 1))
c_good = app.c

with tele.session(), tele.observe(heartbeat=5, flight_dir=fdir):
    # healthy solve first; checkpoint the state it produced
    x, good = app.solve(method="cg", tol=1e-8)
    assert good.status == tele.SolveStatus.CONVERGED
    ckpt.save({"x": x}, 1, out)

    # poison ONE interior coefficient cell on rank 1 (stacked layout:
    # the rank-1 block starts at row 10 of the (20, 10, 10) array)
    c = np.array(app.c)
    c[14, 4, 4] = np.nan
    app.c = jnp.asarray(c)
    x2, bad = app.solve(method="cg", tol=1e-8)
    assert bad.status == tele.SolveStatus.DIVERGED_NONFINITE, bad.status
    assert bad.iterations <= 1, bad.iterations      # early exit, not maxiter

# one flight record per rank, dumped at failure time
files = sorted(glob.glob(os.path.join(fdir, "flight-rank*.jsonl")))
assert [os.path.basename(p) for p in files] == [
    "flight-rank0000.jsonl", "flight-rank0001.jsonl"], files
for p in files:
    lines = [json.loads(ln) for ln in open(p)]
    header, events = lines[0], lines[1:]
    assert header["type"] == "flight_header"
    assert header["reason"] == "status:DIVERGED_NONFINITE"
    assert header["n_events"] == len(events)
    assert "host_peak_rss_kb" in header["memory"]
    # every rank left its device-side final-health verdict behind
    finals = [e for e in events if e.get("type") == "health"]
    assert any(e["status"] == "DIVERGED_NONFINITE" for e in finals), p
# the host-side solve summary (rank 0) carries the residual tail
ev0 = [json.loads(ln) for ln in open(files[0])][1:]
solves = [e for e in ev0 if e.get("type") == "solve"]
assert any(e["status"] == "DIVERGED_NONFINITE" for e in solves)
assert any(e["status"] == "CONVERGED" for e in solves)  # the healthy one

# diag CLI: merge into one clock-aligned Perfetto trace + imbalance report
trace_path = os.path.join(out, "trace.json")
buf = io.StringIO()
with cl.redirect_stdout(buf):
    rc = diag.main([fdir, "--out", trace_path])
assert rc == 0
report = buf.getvalue()
assert "imbalance" in report
trace = json.load(open(trace_path))
evs = trace["traceEvents"]
assert {e["pid"] for e in evs} == {0, 1}          # both ranks merged
assert any(e["ph"] == "X" for e in evs)           # spans survived
assert any(e["ph"] == "i" for e in evs)           # health/heartbeat instants

# checkpoint resume: heal the coefficient, restore the good state, and
# restart clean — warm-started CG reconverges immediately
app.c = c_good
state = ckpt.restore({"x": x}, 1, out)
x3, info3 = app.solve(method="cg", tol=1e-8, x0=state["x"])
assert info3.status == tele.SolveStatus.CONVERGED
assert info3.iterations <= 5, info3.iterations    # warm start: near-instant
print("OK nan flight diag resume")
""",
        ndev=2,
        timeout=900,
    )
    assert "OK nan flight diag resume" in out
