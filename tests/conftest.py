# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real single CPU device. Multi-device behaviour is tested via subprocesses
# (the devices_subprocess fixture) that set
# --xla_force_host_platform_device_count.
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# Property tests import `hypothesis`, which is a declared test dependency
# (pyproject.toml) but absent from minimal images. Fall back to the vendored
# mini implementation so collection never fails on a clean checkout.
try:
    import hypothesis  # noqa: F401
except ModuleNotFoundError:
    import importlib.util

    _spec = importlib.util.spec_from_file_location(
        "hypothesis", os.path.join(os.path.dirname(__file__), "_mini_hypothesis.py")
    )
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis"] = _mod


def run_devices_subprocess(code: str, n_devices: int = 8, timeout: int = 600):
    """Run `code` in a subprocess with n fake CPU devices; returns stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    if proc.returncode != 0:
        raise AssertionError(f"subprocess failed:\n{proc.stdout}\n{proc.stderr}")
    return proc.stdout


@pytest.fixture
def devices_subprocess():
    return run_devices_subprocess
