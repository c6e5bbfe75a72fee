"""Fixtures of the benchmark's own tests: the tiny tree, and a compile
cache left off (the harness points JAX's persistent cache at the
checkout; the tests compile small programs and keep nothing)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def no_compile_cache(monkeypatch):
    import repro.launch.jax_cache as jax_cache

    monkeypatch.setattr(jax_cache, "enable_compile_cache", lambda: None)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    from chipbench.tests.tiny import make_tree

    return make_tree(str(tmp_path_factory.mktemp("tiny")))
