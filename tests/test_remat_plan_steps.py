"""The six tiny steps under the remat plan (``ray_tpu/models/remat.py``):
each step's numbers under a generous plan equal those under today's two
names and under ``remat=False``; and what the plan costs as a count: a
planned trace calls the objective, each layer's function and each
kernel's forward no more often than an unplanned one.  (Out of
``tests/test_remat_plan.py``, unchanged, so that ``--dist loadfile`` can
give the two a worker of their own.)"""

import collections
import functools

import jax
import numpy as np
import pytest

from ray_tpu.models import remat, transformer
from tiny_steps import (ROOM, RUNS, _device, _tiny_step,  # noqa: F401
                        every_candidate_that_spares_anything)

STEPS = ("dense", "block_diffusion", "latent", "hybrid", "sambay",
         "windowed", "nemotron")


def _counting(monkeypatch, module, name, counts):
    real = getattr(module, name)

    @functools.wraps(real)
    def counted(*args, **kwargs):
        counts[name] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(module, name, counted)


@pytest.mark.parametrize("kind", STEPS)
def test_planning_a_step_calls_no_function_more_often(kind, monkeypatch):
    """The plan's cost as a count, not a clock: tracing a planned step
    calls each layer's function (``apply_layer``), the loss
    (``loss_and_counters`` or the override) and the reader of a layer
    (``survey``: once a run, Python over a jaxpr that exists) as often
    as the runs are, and the objective once -- what the unplanned trace
    does.  A second trace of the same shapes reads no layer again and
    gets the plan of the first."""
    counts = collections.Counter()
    _counting(monkeypatch, transformer, "apply_layer", counts)
    _counting(monkeypatch, remat, "survey", counts)
    real = jax.make_jaxpr
    monkeypatch.setattr(remat.jax, "make_jaxpr", lambda *a, **k: (
        counts.update(["make_jaxpr"]), real(*a, **k))[1])
    calls = {}
    for how, memory in (("today", None), ("planned", ROOM)):
        _device(monkeypatch, memory)
        step, state, batch = _tiny_step(kind)
        counts.clear()
        step.lower(state, batch)
        calls[how] = dict(counts)
    runs = len(RUNS[kind])
    assert calls["today"] == {"apply_layer": runs}
    assert calls["planned"] == {"apply_layer": runs, "survey": runs,
                                "make_jaxpr": 1}
    # the same step traced again (other arguments' weak types, say)
    counts.clear()
    first = dict(step._kept)
    jax.clear_caches()
    step.lower(state, batch)
    assert counts == {"apply_layer": runs, "make_jaxpr": 1}
    assert {**step._kept, "trace_seconds": 0} == {**first, "trace_seconds": 0}


@pytest.mark.parametrize("kind", STEPS)
def test_a_planned_step_is_the_step_without_remat(kind, monkeypatch):
    """The four tiny steps (dense; block-diffusion experts; latent
    attention, shared expert and the multi-token module; delta layers
    beside gated attention in a period) under a plan with room for
    everything, under today's two names and with ``remat_layer`` taken
    out: the same loss, gradient norm and counters, to the tolerance of
    ``tests/test_block_diffusion.py``'s remat test, and the same new
    parameters to a thirtieth of one AdamW update (a gradient near zero
    moves its update by more than its own rounding)."""
    got = {}
    for how in ("planned", "today", "no remat"):
        _device(monkeypatch, ROOM if how == "planned" else None)
        if how == "no remat":
            monkeypatch.setattr(transformer, "remat_layer",
                                lambda layer, cfg, *a, **k: layer)
        step, state, batch = _tiny_step(kind)
        new, metrics = step(state, batch)
        got[how] = jax.device_get((metrics, new["params"]))
        if how == "planned":
            plan = step._kept
            assert sorted(r["kind"] for r in plan["runs"]) == RUNS[kind]
            # (a layer that is its mixer alone has no middle residual:
            # its sum is the layer's output)
            assert all(("mid_residual" in r["names"])
                       != r["kind"].endswith("+none") and r["refused"] == []
                       for r in plan["runs"])
        else:
            assert step._kept == remat.no_plan()
    for other in ("today", "no remat"):
        for part, atol in ((0, 1e-6), (1, 1e-5)):
            for a, b in zip(jax.tree.leaves(got["planned"][part]),
                            jax.tree.leaves(got[other][part])):
                assert np.all(np.isfinite(a))
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=atol)
