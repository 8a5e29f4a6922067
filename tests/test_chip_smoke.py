"""``chip_smoke.py`` at a tiny size on the CPU.

Its phases drive the counting deployment end to end through ``App``
with the Pallas kernels in interpret mode and hold every touched slate
to the numpy reference; the sharded phase runs on four virtual CPU
devices in a subprocess (so this process keeps its single device).  The
script itself must refuse to run, and print no result, without a TPU
or outside the repository.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import chip_smoke as cs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = cs.Sizes(capacity=4096, n_keys=2000, batch=256, ticks=16,
                kernels="interpret")


def _env(**extra):
    return {**os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "src")]),
            **extra}


def test_counting_phase_matches_numpy():
    cs.phase_counting(TINY, np.random.default_rng(0))


def test_durable_phase_recovers_acknowledged_counts(tmp_path):
    cs.phase_durable(TINY, np.random.default_rng(1), str(tmp_path))


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent(f"""
        import numpy as np
        import chip_smoke as cs
        cs.phase_four_chips(cs.Sizes(capacity=4096, n_keys=2000,
                                     batch=256, ticks=16,
                                     kernels="interpret"),
                            np.random.default_rng(2))
        print("FOUR-OK")
    """)
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, timeout=300,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0, r.stderr
    assert "FOUR-OK" in r.stdout
    assert '"exchange_dropped": 0' in r.stdout


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_a_tpu(tmp_path, where):
    script = os.path.join(ROOT, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path)
    r = subprocess.run([sys.executable, script], capture_output=True,
                       text=True, cwd=tmp_path, timeout=120,
                       env={**_env(), "PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
