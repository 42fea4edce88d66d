"""Scheduling kernel package.

- ``jax_backend`` — the device-resident batch solver (waterfill +
  bundle packing) every scheduler surface routes through; owns the
  single-device jit kernels and the dirty-row delta path.
- ``sharded_solve`` — the pod-sharded solve (ISSUE 17): above
  ``solver_shard_min_nodes`` the (classes x nodes) matrices shard along
  the node axis over a 1-D device mesh via ``shard_map``.
- ``bundle_packing`` — placement-group bundle packing strategies.
- ``policy`` / ``resources`` — host-side policy glue and resource
  vector shapes.

Submodules are imported lazily: ``jax_backend``/``sharded_solve`` pull
in jax at import time, and control-plane processes that never solve
(log monitor, dashboard) must not pay that.
"""

import importlib

_SUBMODULES = ("bundle_packing", "jax_backend", "policy", "resources",
               "sharded_solve")


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"{__name__}.{name}")
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES))
