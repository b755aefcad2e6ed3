"""`chip_smoke.py`: refuses to run without a TPU, and its phases' checks
hold on the CPU at the reduced config.  Also where the entry points keep
the persistent compilation cache."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert "phase" not in p.stdout
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("from_env", [False, True], ids=["checkout", "env"])
def test_compile_cache_dir(tmp_path, from_env):
    """`JAX_COMPILATION_CACHE_DIR` when set (nothing else is set),
    otherwise the fixed, git-ignored `.jax_cache/` of the checkout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(ROOT, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    code = ("import jax; from repro.launch.compile_cache import "
            "enable_compile_cache as e; "
            "print(e()); print(jax.config.jax_compilation_cache_dir)")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [want, want]
    if not from_env:
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


@pytest.fixture
def smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(ROOT)


def test_phases_pass_at_reduced_size(smoke, tmp_path):
    train = smoke.train_phase(str(tmp_path / "train"), reduced=True)
    assert train["saves"] == smoke.STEPS // smoke.SAVE_EVERY
    assert train["digests_cross_checked"] > 0
    serve = smoke.serve_phase(str(tmp_path / "serve"), reduced=True)
    assert serve["tokens"] == smoke.GEN_TOKENS
    assert serve["cache_bytes"] > 0
