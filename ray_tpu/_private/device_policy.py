"""Who holds the chip, and where compiled programs are kept.

One process per chip.  The process that called ``ray_tpu.init`` owns
the accelerator; in the supported shape (``worker_process_mode=
"thread"``) the raylet's scheduling solve and the Train worker's model
step share it as threads of that process.  A second process that opens
the chip its parent holds fails or hangs, so every child the runtime
starts (``node_host``, ``worker_main``, job drivers) takes its
environment from :func:`child_env`, which pins it to the CPU unless the
caller hands it the chip explicitly.  Process-mode workers that need
the chip are not supported yet (ROADMAP D7).

The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, and otherwise at one fixed directory inside the checkout: the
path is part of the cache key, so a directory that moves never hits.
It is for accelerator programs (10-14 s each at full width).  A process
pinned to the CPU is left without it unless the environment names one:
XLA:CPU cache entries are native code for the machine that compiled
them (jaxlib 0.9.0's loader logs a machine-feature mismatch on every
load and warns of SIGILL), and what those processes compile is small.
"""

from __future__ import annotations

import os
from typing import Dict

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The ONE place the compile-cache path is decided: the environment's
    if set, else ``<checkout>/.jax_cache`` (git-ignored) — never a
    temporary name, a pid or a clock."""
    from ray_tpu._private.runtime_env import framework_import_root
    return os.environ.get(_CACHE_ENV) or os.path.join(
        framework_import_root(), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir`; returns the directory.  When the
    environment names it JAX has already read it and nothing is set in
    code; a process pinned to the CPU is left alone (module docstring).
    Call before the process's first compile: the entry scripts, the
    solvers' constructors and ``ray_tpu.ops`` (at import) do."""
    path = compile_cache_dir()
    if not os.environ.get(_CACHE_ENV):
        import jax
        if jax.config.jax_platforms != "cpu":
            jax.config.update("jax_compilation_cache_dir", path)
    return path


def child_env(runtime_env_ctx=None) -> Dict[str, str]:
    """Environment for a child process of the runtime.

    Inherits ``os.environ``, can ``import ray_tpu`` from any cwd, and
    is pinned to the CPU (``JAX_PLATFORMS=cpu``).  Only the child's
    materialized runtime_env (``RuntimeEnvContext``: ``env_vars`` the
    user set on purpose for it, import paths, cwd) can hand it the
    chip; an inherited ``JAX_PLATFORMS`` never does.  A child that does
    get the chip shares this process's compile cache; one on the CPU
    gets none (module docstring)."""
    from ray_tpu._private.runtime_env import framework_import_root
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if runtime_env_ctx is not None:
        env = runtime_env_ctx.spawn_env(env)
    if env["JAX_PLATFORMS"] == "cpu":
        env.pop(_CACHE_ENV, None)
    else:
        env[_CACHE_ENV] = compile_cache_dir()
    env["PYTHONPATH"] = framework_import_root() + os.pathsep + \
        env.get("PYTHONPATH", "")
    return env
