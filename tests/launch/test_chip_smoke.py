"""chip_smoke.py: its phases at a tiny grid on the CPU, and its refusal to
run anywhere but on a TPU.

``main()`` alone checks the platform; ``one_chip_phase`` and
``four_chip_phase`` take the grid and the devices, so these tests drive the
same code the chip runs, at sizes the CPU (and interpret-mode Pallas) can
afford.
"""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_smoke_refuses_without_tpu(tmp_path, where):
    """On the CPU, and in a directory holding only the script, it exits
    nonzero and prints no result line."""
    if where == "alone":
        script = tmp_path / "chip_smoke.py"
        shutil.copy(SMOKE, script)
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    else:
        script = SMOKE
        env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, str(script)], cwd=os.path.dirname(str(script)),
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "needs a TPU" in p.stderr


def test_one_chip_phase_tiny_grid(capsys):
    """XLA vs interpret-mode Pallas build, served and flushed side by side."""
    _load_smoke().one_chip_phase(8, jax.devices()[0], rounds=2, batch=64)
    out = capsys.readouterr().out
    assert "n: 64" in out and "warm_flush_s_median" in out


_FOUR = """
import importlib.util
import jax
spec = importlib.util.spec_from_file_location("chip_smoke", {smoke!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
smoke.four_chip_phase(10, jax.devices()[:4], rounds=4, batch=4096)
print("FOUR_OK")
"""


def test_four_chip_phase_tiny_grid(devices_subprocess):
    """Sharded plans over four forced host devices vs the one-device engine:
    repartition, collective halo, balanced and replicated gathers."""
    out = devices_subprocess(_FOUR.format(smoke=SMOKE), n_devices=4)
    assert "FOUR_OK" in out
