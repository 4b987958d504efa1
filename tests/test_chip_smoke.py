"""``chip_smoke.py`` prints no result off the chip, and the persistent
compile cache is placed from outside the program."""
import os
import shutil
import subprocess
import sys

import pytest

from repro.launch._xla_env import CACHE_DIRNAME, CACHE_ENV, use_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_a_tpu(tmp_path, alone):
    """On the CPU, and as a lone copy with no package beside it, the
    script exits non-zero with a readable message and no JSON line."""
    script = os.path.join(REPO, "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if alone:
        script = shutil.copy(script, tmp_path)
        env.pop("PYTHONPATH", None)
    out = subprocess.run([sys.executable, script], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "chip_smoke: FAIL:" in out.stderr
    if not alone:
        assert "no TPU" in out.stderr


def test_compile_cache_set_from_outside_is_left_alone(monkeypatch):
    monkeypatch.setenv(CACHE_ENV, os.path.join(REPO, "elsewhere"))
    assert use_compile_cache() == os.path.join(REPO, "elsewhere")
    assert os.environ[CACHE_ENV] == os.path.join(REPO, "elsewhere")


def test_compile_cache_defaults_to_a_fixed_checkout_dir(monkeypatch):
    """Unset, the cache goes to ``<checkout>/.jax_cache`` — the same path
    every time (the path is part of what a cache hit matches), set in the
    environment so child processes inherit it, and git-ignored."""
    want = os.path.join(REPO, CACHE_DIRNAME)
    for _ in range(2):
        monkeypatch.delenv(CACHE_ENV, raising=False)
        assert use_compile_cache() == want
        assert os.environ[CACHE_ENV] == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert f"{CACHE_DIRNAME}/" in f.read().split()
