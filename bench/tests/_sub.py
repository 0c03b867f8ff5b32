"""Run a benchmark script in a child process on CPU devices."""

from __future__ import annotations

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(script: str, *args, timeout: int = 600, cwd: str | None = None,
        check: bool = True) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, script, *map(str, args)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=cwd)
    if check and proc.returncode != 0:
        raise AssertionError(f"{script} exited {proc.returncode}:\n"
                             f"{proc.stdout[-3000:]}\n{proc.stderr[-3000:]}")
    return proc


def last_json(proc) -> dict:
    return json.loads(proc.stdout.strip().splitlines()[-1])


def json_lines(proc) -> list:
    return [json.loads(x) for x in proc.stdout.splitlines()
            if x.startswith("{")]
