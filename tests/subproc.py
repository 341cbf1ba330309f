"""Run a child Python process from a test.

The child inherits this process's environment (``JAX_PLATFORMS`` and the
rest) with the repository's ``src`` put first on ``PYTHONPATH``, and runs
from the repository root, wherever the checkout lives.  Children that set
``XLA_FLAGS`` (virtual CPU devices) do so themselves, before importing jax.
"""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def run_python(args, *, timeout=1800):
    """``python *args`` in a child; returns the CompletedProcess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO / "src"), str(REPO), env.get("PYTHONPATH"))
        if p)
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=REPO, timeout=timeout)
