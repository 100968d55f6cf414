"""Shared test bootstrap.

Forces 8 XLA host devices before any test module imports jax, so the
mesh-based tests (test_distributed.py and any in-process mesh
construction) run on CPU.  ``setdefault`` keeps an operator's explicit
XLA_FLAGS intact; the test_distributed subprocesses overwrite the flag
themselves, so they are unaffected.
"""

import os
import subprocess
import sys
import textwrap

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_forced_subprocess(body: str, n_devices: int = 8) -> str:
    """Run a test body in a subprocess with ``n_devices`` forced XLA host
    devices (device count must be set before jax initializes, so mesh-count
    experiments can't run in-process).  Shared by test_distributed.py and
    test_elastic.py; asserts exit 0 and returns stdout."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={n_devices}"
        import functools, shutil, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=os.path.join(_ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout
