"""Run a snippet in a subprocess with N fake XLA host devices.

Multi-device tests must set ``--xla_force_host_platform_device_count``
BEFORE jax initializes; the pytest process itself keeps 1 device (per the
project convention that smoke tests/benches see a single device), so every
distributed test runs through this helper.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")

PRELUDE = """
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("_REPRO_XLA_EXTRA", "")
    + " --xla_force_host_platform_device_count={ndev} {xla_flags}"
)
import jax
jax.config.update("jax_platform_name", "cpu")
import numpy as np
import jax.numpy as jnp
"""


def run(snippet: str, ndev: int = 8, timeout: int = 600,
        xla_flags: str = "") -> str:
    """Execute ``snippet`` with ``ndev`` devices; returns stdout.

    The snippet should use plain ``assert``/prints; a non-zero exit fails
    the calling test with full output attached.  ``xla_flags`` are
    appended to the subprocess's ``XLA_FLAGS``.
    """
    code = PRELUDE.format(ndev=ndev, xla_flags=xla_flags) \
        + textwrap.dedent(snippet)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={proc.returncode})\n--- code ---\n{code}\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr[-8000:]}"
        )
    return proc.stdout
