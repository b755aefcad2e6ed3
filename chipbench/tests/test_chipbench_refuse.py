"""On a backend that is not a TPU the benchmark measures nothing."""
import os
import subprocess
import sys

from chipbench import harness


def test_run_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.PKG, "run.py"), "--workload",
         "train-finetune-top2", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=harness.ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "{" not in p.stdout


def test_devices_refuses_too_few_chips():
    import jax
    import pytest
    with pytest.raises(harness.NoAccelerator):
        harness.devices(1)
    with pytest.raises(harness.NoAccelerator):
        harness.devices(len(jax.devices()) + 1, require_tpu=False)
