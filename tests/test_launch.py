"""Entry-point contracts: the triage CLI fails loudly when a submit
failed, and the compile cache lives where it is told to, or at one
fixed place in the checkout."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import jax_cache, serve

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_serve_main_exits_nonzero_when_a_submit_fails(
    monkeypatch, tmp_path, restore_cache_config
):
    def fail(self, *a, **kw):
        raise RuntimeError("injected tick fault")

    monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path / "cache"))
    monkeypatch.setattr(serve.DetectionService, "submit", fail)
    with pytest.raises(SystemExit) as exc:
        serve.main(
            ["--scale", "0.05", "--batch", "64", "--max-batches", "2",
             "--submitters", "1", "--witnesses", "0"]
        )
    assert exc.value.code not in (0, None)
    assert "2 of 2 submits failed" in str(exc.value.code)
    assert "injected tick fault" in str(exc.value.code)


def test_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path))
    assert jax_cache.compile_cache_dir() == str(tmp_path)


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    first, second = jax_cache.compile_cache_dir(), jax_cache.compile_cache_dir()
    assert first == second
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert first == os.path.join(root, ".jax_cache")


def test_enable_writes_only_to_env_dir(tmp_path):
    """With the variable set, a compile lands in that directory."""
    cache = tmp_path / "cache"
    code = (
        "from repro.launch.jax_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    env[jax_cache.ENV_VAR] = str(cache)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == str(cache)
    assert any(cache.iterdir())
    assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]
