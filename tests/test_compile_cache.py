"""The one compile-cache rule (calfkit_tpu/inference/compile_cache.py)."""

from __future__ import annotations

import os
import re
import subprocess
import sys

import jax
import pytest

from calfkit_tpu.inference import compile_cache
from calfkit_tpu.inference.config import RuntimeConfig, preset
from calfkit_tpu.inference.engine import InferenceEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_updates(monkeypatch):
    """Every value the code under test hands to
    ``jax.config.update("jax_compilation_cache_dir", ...)`` (recorded, not
    applied — the CPU lane keeps no persistent cache)."""
    seen: list = []
    real = jax.config.update

    def update(name, value):
        if name == "jax_compilation_cache_dir":
            seen.append(value)
        else:
            real(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    return seen


def _engine(**kw) -> InferenceEngine:
    return InferenceEngine(
        preset("debug"),
        RuntimeConfig(max_batch_size=2, max_seq_len=64, prefill_chunk=16, **kw),
    )


class TestCompileCacheRule:
    def test_env_var_set_code_sets_no_directory(
        self, monkeypatch, cache_dir_updates
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
        assert compile_cache.enable_compile_cache() == "/x"
        _engine()
        assert cache_dir_updates == []

    def test_unset_uses_the_fixed_in_checkout_path(
        self, monkeypatch, cache_dir_updates
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.enable_compile_cache() == compile_cache.CACHE_DIR
        _engine()
        assert cache_dir_updates == [compile_cache.CACHE_DIR] * 2
        assert compile_cache.CACHE_DIR == os.path.join(REPO, ".jax_cache")

    def test_path_has_no_host_pid_temp_or_time_component(self):
        """The path is part of the cache's key: a second interpreter (other
        pid, later time) computes the identical string, and nothing in it
        comes from the host."""
        other = subprocess.run(
            [sys.executable, "-c",
             "from calfkit_tpu.inference.compile_cache import CACHE_DIR; "
             "print(CACHE_DIR)"],
            capture_output=True, text=True, check=True, cwd=REPO,
            env={**os.environ, "PYTHONPATH": REPO, "TMPDIR": "/nonexistent",
                 "HOME": "/nonexistent"},
        ).stdout.strip()
        assert other == compile_cache.CACHE_DIR
        tail = os.path.relpath(compile_cache.CACHE_DIR, REPO)
        assert tail == ".jax_cache"
        assert not re.search(r"\d", tail)
        assert os.uname().nodename not in compile_cache.CACHE_DIR

    @pytest.mark.parametrize("placed", ["/x", None])
    def test_locations_carry_one_frame_not_a_traceback(
        self, monkeypatch, cache_dir_updates, placed
    ):
        """A Pallas kernel's locations are part of the bytes the cache key
        hashes: ten frames of traceback there made the key depend on which
        program traced the kernel first (tests/test_tpu_compile.py holds
        the bytes to it).  The name stack, which the device trace reads its
        scopes from, stays."""
        import jax.numpy as jnp

        option = "jax_traceback_in_locations_limit"
        before = getattr(jax.config, option)
        if placed:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            jax.config.update(option, 10)
            compile_cache.enable_compile_cache()
            assert getattr(jax.config, option) == 1

            @jax.named_scope("decode_loop")
            def scoped(x):
                with jax.named_scope("mlp"):
                    return jnp.dot(x, x)

            text = jax.jit(scoped).lower(jnp.ones((4, 4))).compile().as_text()
            assert "decode_loop/mlp/dot_general" in text
        finally:
            jax.config.update(option, before)

    def test_directory_is_git_ignored(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_runtime_switch_only_turns_it_off(
        self, monkeypatch, cache_dir_updates
    ):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        _engine(compilation_cache=False)
        assert cache_dir_updates == []
        assert not hasattr(RuntimeConfig(), "compilation_cache_dir")

    def test_failure_to_enable_is_not_swallowed(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

        def boom(name, value):
            raise RuntimeError("cache cannot be enabled")

        monkeypatch.setattr(jax.config, "update", boom)
        with pytest.raises(RuntimeError, match="cannot be enabled"):
            _engine()

    def test_no_other_call_site_sets_a_cache_directory(self):
        """One helper: nothing else in the program names the option."""
        hits = []
        for top in ("calfkit_tpu", "scripts", "chip_smoke.py",
                    "__graft_entry__.py", "conftest.py", "examples"):
            path = os.path.join(REPO, top)
            files = [path] if os.path.isfile(path) else [
                os.path.join(d, f) for d, _, fs in os.walk(path)
                for f in fs if f.endswith(".py")
            ]
            for file in files:
                with open(file) as f:
                    if "jax_compilation_cache_dir" in f.read():
                        hits.append(os.path.relpath(file, REPO))
        assert hits == ["calfkit_tpu/inference/compile_cache.py"]
