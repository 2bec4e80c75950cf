import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 300):
    """Run a python snippet in a subprocess with N fake host devices
    (jax locks the device count at first init, so multi-device tests need
    their own process)."""
    import os
    env = dict(os.environ)
    env.update({"XLA_FLAGS":
                f"--xla_force_host_platform_device_count={n_devices}",
                "PYTHONPATH": str(REPO / "src"),
                # the fake devices are host devices; never the chip, which
                # the test process itself may hold
                "JAX_PLATFORMS": "cpu"})
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def random_sparse(rng, n, m, density, dtype=np.float32):
    a = (rng.random((n, m)) < density) * rng.standard_normal((n, m))
    return a.astype(dtype)
