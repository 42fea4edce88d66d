"""One process per chip, one place for the compile cache
(``ray_tpu._private.device_policy``)."""

import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from ray_tpu._private import device_policy
from ray_tpu._private.runtime_env import framework_import_root

_CACHE = "JAX_COMPILATION_CACHE_DIR"


def test_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv(_CACHE, raising=False)
    want = os.path.join(framework_import_root(), ".jax_cache")
    assert device_policy.compile_cache_dir() == want
    assert device_policy.compile_cache_dir() == want      # every call
    # ...and in another process, from another cwd.
    out = subprocess.run(
        [sys.executable, "-c",
         "from ray_tpu._private.device_policy import compile_cache_dir;"
         "print(compile_cache_dir())"],
        env=device_policy.child_env(), cwd="/", capture_output=True,
        text=True, timeout=60,
        check=True)
    assert out.stdout.strip() == want
    with open(os.path.join(framework_import_root(), ".gitignore")) as f:
        assert "/.jax_cache/" in f.read().split()


def test_environment_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path):
    import jax
    monkeypatch.setenv(_CACHE, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device_policy.compile_cache_dir() == str(tmp_path)
    assert device_policy.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cpu_pinned_process_is_left_without_a_cache(monkeypatch):
    import jax
    monkeypatch.delenv(_CACHE, raising=False)
    assert jax.config.jax_platforms == "cpu"               # conftest
    before = jax.config.jax_compilation_cache_dir
    device_policy.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_accelerator_process_gets_the_in_checkout_cache(monkeypatch):
    monkeypatch.delenv(_CACHE, raising=False)
    updates = {}
    monkeypatch.setattr(
        device_policy, "compile_cache_dir", lambda: "/fixed/.jax_cache")
    fake = SimpleNamespace(config=SimpleNamespace(
        jax_platforms=None, update=updates.__setitem__))
    monkeypatch.setitem(sys.modules, "jax", fake)
    assert device_policy.enable_compile_cache() == "/fixed/.jax_cache"
    assert updates == {"jax_compilation_cache_dir": "/fixed/.jax_cache"}


def test_children_are_pinned_to_the_cpu(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")     # inherited: not a grant
    monkeypatch.setenv(_CACHE, str(tmp_path))
    env = device_policy.child_env()
    assert env["JAX_PLATFORMS"] == "cpu"
    assert _CACHE not in env
    assert env["PYTHONPATH"].split(os.pathsep)[0] == framework_import_root()


def test_a_runtime_env_can_hand_the_child_the_chip(monkeypatch):
    from ray_tpu._private.runtime_env import RuntimeEnvContext
    monkeypatch.delenv(_CACHE, raising=False)
    env = device_policy.child_env(RuntimeEnvContext(
        env_vars={"JAX_PLATFORMS": "tpu"}, cwd=None,
        import_paths=["/some/pkg"]))
    assert env["JAX_PLATFORMS"] == "tpu"
    assert env[_CACHE] == device_policy.compile_cache_dir()
    assert env["PYTHONPATH"].split(os.pathsep)[:2] == \
        [framework_import_root(), "/some/pkg"]


@pytest.mark.parametrize("site", [
    "ray_tpu/_private/cluster.py", "ray_tpu/_private/worker_pool.py",
    "ray_tpu/job_submission/__init__.py"])
def test_every_spawn_site_uses_the_helper(site):
    with open(os.path.join(framework_import_root(), site)) as f:
        src = f.read()
    assert "child_env(" in src and "dict(os.environ)" not in src
