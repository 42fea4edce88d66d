"""What a rematerialised layer keeps (``ray_tpu/models/remat.py``): the
planner as a pure function of surveys and a budget; for each kind of
layer a two-layer scan whose numbers under a generous plan equal those
under today's two names and under ``remat=False``, and what the plan
keeps, refuses and counts.  The six tiny steps themselves, planned
against unplanned, are ``tests/test_remat_plan_steps.py``: a file of
their own so that ``--dist loadfile`` can give them another worker."""

import ast
import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import mha, remat, transformer
from ray_tpu.models.gdn import GDNConfig
from ray_tpu.models.kda import KDAConfig
from ray_tpu.models.mamba import MambaConfig
from ray_tpu.models.mamba2 import Mamba2Config
from ray_tpu.models.mla import MLAConfig
from ray_tpu.models.transformer import (TransformerConfig, apply_layer,
                                        init_stack, run_stack)
from ray_tpu.ops import gated_delta, kda, ssd
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES
from tiny_steps import (FULL, ROOM, RUNS, _device,  # noqa: F401
                        _tiny_step, every_candidate_that_spares_anything)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BASE = dict(vocab_size=64, d_model=32, n_heads=2, d_ff=48, max_seq_len=32,
             dtype=jnp.float32, context_parallel=False)
_MAMBA = MambaConfig(d_inner=64, d_state=4, d_conv=4, dt_rank=4, chunk=8)
# kind -> (the run's (attention, ffn), the configuration, names a plan
# with room for everything has to keep at these sizes).  What it leaves
# spares nothing once what is upstream of it is kept.
KINDS = {
    "mha": (("mha", "dense"), dict(n_layers=2),
            {"mid_residual", "attn_q", "attn_k", "attn_v", "ffn_gate",
             "ffn_up"}),
    "mha-gated": (("mha", "dense"),
                  dict(n_layers=2, attn_out_gate=True, qk_norm=True,
                       head_dim=16, rotary_dim=8, norm_plus_one=True),
                  {"mid_residual", "attn_v", "ffn_gate", "ffn_up"}),
    "mla": (("mla", "dense"),
            dict(n_layers=2, mla=MLAConfig(
                q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16)),
            {"mid_residual", "mla_q_down", "mla_kv_down", "mla_k_rope",
             "ffn_gate", "ffn_up"}),
    "gdn": (("gdn", "dense"),
            dict(layer_pattern=(("gdn", "dense", 2),), gdn=GDNConfig(
                num_key_heads=2, num_value_heads=4, key_head_dim=16,
                value_head_dim=16, chunk=16)),
            {"mid_residual", "gdn_ba", "gdn_qkvz", "ffn_gate", "ffn_up"}),
    "moe-shared": (("mha", "moe"),
                   dict(n_layers=2, moe_experts=4, moe_top_k=2,
                        moe_shared_width=16, moe_shared_gate=True),
                   {"mid_residual", "moe_scores", "moe_shared_gate",
                    "moe_shared_up", "attn_q", "attn_k", "attn_v"}),
    "moe-sigmoid": (("mha", "moe"),
                    dict(n_layers=2, moe_experts=4, moe_top_k=2,
                         moe_scoring="sigmoid", moe_bias_rate=0.01),
                    {"mid_residual", "moe_scores", "attn_v"}),
    "mamba": (("mamba:writes=memory", "dense"),
              dict(layer_pattern=(("mamba:writes=memory", "dense", 2),),
                   mamba=_MAMBA, norm="layernorm"),
              {"mid_residual", "ssm_in", "ssm_conv", "ssm_dbc", "ffn_gate",
               "ffn_up"}),
    "gmu": (("gmu", "dense"),
            dict(layer_pattern=(("mamba:writes=memory", "dense", 1),
                                ("gmu", "dense", 2)), mamba=_MAMBA),
            {"mid_residual", "gmu_gate", "ffn_gate", "ffn_up"}),
    "diff-window": (("diff:window=8,writes=kv", "dense"),
                    dict(layer_pattern=(("diff:window=8,writes=kv", "dense",
                                         2),), n_heads=4, n_kv_heads=2,
                         rope="none"),
                    {"mid_residual", "diff_q", "diff_kv", "ffn_gate",
                     "ffn_up"}),
    "mha-window": (("mha:heads=6,window=8,rope=near", "dense"),
                   dict(layer_pattern=(("mha", "dense", 1),
                                       ("mha:heads=6,window=8,rope=near",
                                        "dense", 2)),
                        n_kv_heads=2, head_dim=8, attn_out_gate="head",
                        rope_tables={"near": transformer.RopeTable(100.0)}),
                   {"mid_residual", "attn_q", "attn_k", "attn_v",
                    "attn_head_gate", "ffn_gate", "ffn_up"}),
    "mamba2-latent": (("mamba2", "moe"),
                      dict(layer_pattern=(("mamba2", "moe", 2),),
                           mamba2=Mamba2Config(num_heads=4, head_dim=8,
                                               n_groups=2, state_size=8,
                                               chunk=16, norm_groups=2),
                           moe_experts=4, moe_top_k=2, moe_shared_width=16,
                           moe_act="relu2", moe_latent=16,
                           moe_scoring="sigmoid", moe_bias_rate=0.01),
                      {"mid_residual", "ssd_z", "ssd_xbc", "moe_scores",
                       "moe_latent", "moe_latent_out"}),
    # (heads of 8: at 16 the three programs' ``w_qkv`` gradients, whose
    # largest entry is 54.6, differ by up to 9.5e-6 on entries near 0.4,
    # 1.7e-7 of the leaf's scale, a float32 rounding of sums XLA orders
    # differently (the GDN case differs by 1.3e-5 of its scale), which the
    # elementwise atol of 5e-6 does not scale to)
    "kda-grouped": (("kda", "moe"),
                    dict(layer_pattern=(("kda", "moe", 2),),
                         kda=KDAConfig(num_heads=2, head_dim=8, chunk=16),
                         moe_experts=8, moe_top_k=2, moe_shared_width=16,
                         moe_scoring="sigmoid", moe_bias_rate=0.01,
                         moe_n_group=4, moe_topk_group=2),
                    {"mid_residual", "kda_qkv", "kda_alpha",
                     "kda_beta_gate", "moe_scores", "moe_shared_gate",
                     "moe_shared_up"}),
    "diff-cross": (("diff:reads=kv", "dense"),
                   dict(layer_pattern=(("diff:writes=kv", "dense", 1),
                                       ("diff:reads=kv", "dense", 2)),
                        n_heads=4, n_kv_heads=2, tie_embeddings=True),
                   {"mid_residual", "diff_q", "ffn_gate", "ffn_up"}),
}
#: What the kinds that read the shared slot are handed (rows 2, the
#: tests' length 32): a scan output; keys and values.
SLOTS = {
    "gmu": lambda: {"memory": jnp.full((2, 32, 64), 0.5, jnp.float32)},
    "diff-cross": lambda: {"kv": tuple(
        jax.random.normal(jax.random.PRNGKey(7 + i), (2, 32, 1, w))
        for i, w in enumerate((8, 8, 16)))},
}
def _two_layers(kind, remat_on=True, length=32, **more):
    """-> (cfg, loss(x, stack), x, stack): the scan over two layers of
    the kind, as ``run_layers`` runs it."""
    run, extra, _ = KINDS[kind]
    cfg = TransformerConfig(**{**_BASE, **extra, **more, "remat": remat_on,
                               "max_seq_len": length})
    stack = init_stack(jax.random.PRNGKey(2), cfg, *run, 2)
    # (norm weights off their start, so that their gradients say something)
    stack = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(a.size),
                                               a.shape, a.dtype), stack)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, length, cfg.d_model),
                          jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(length, dtype=jnp.int32)[None],
                                 (2, length))

    def loss(x, stack):
        return jnp.sum(run_stack(
            x, stack, run, positions, cfg, first_index=3,
            shared=SLOTS.get(kind, dict)())[0] ** 2)

    return cfg, loss, x, stack


def _planned(loss, reports=None, mesh=None):
    """``jax.grad(loss, (0, 1))`` as a step takes it: through
    ``remat.value_and_grad``; the plans it got land on ``reports``."""
    def grads(x, stack):
        (_, got), report = remat.value_and_grad(
            lambda p: (loss(*p), {}), (x, stack), mesh)
        if reports is not None:
            reports.append(report)
        return got

    return grads


def _names_in(jaxpr, found=None):
    """name -> how many ``checkpoint_name`` equations of it the jaxpr
    and everything it calls hold."""
    found = collections.Counter() if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            found[eqn.params["name"]] += 1
        for value in eqn.params.values():
            for item in (value if isinstance(value, (tuple, list))
                         else (value,)):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    _names_in(inner, found)
    return found


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_generous_plan_changes_no_number_and_spares_the_named_products(
        kind, monkeypatch):
    """Gradients of the two-layer scan under a plan with room for every
    candidate, under today's plan (no device to ask: the flash kernel's
    two names, which the reference attention here does not even make)
    and under ``remat=False`` are the same numbers (to a float32
    rounding: XLA fuses, and so sums, the three programs differently, as
    PR 31 found on the chip).  In the gradient's jaxpr a product the
    plan keeps is named once (the forward scan), one it leaves twice
    (again in the backward scan's recomputation) unless the backward
    needs it no more; without remat nothing is made twice."""
    got, named, reports = {}, {}, []
    for how in ("generous", "today", "no remat"):
        _, loss, x, stack = _two_layers(kind, remat_on=how != "no remat")
        _device(monkeypatch, ROOM if how == "generous" else None)
        fn = _planned(loss, reports)
        named[how] = _names_in(jax.make_jaxpr(fn)(x, stack).jaxpr)
        got[how] = jax.jit(fn)(x, stack)
    (run,) = reports[0]["runs"]
    wanted = set(run["names"])
    assert wanted >= KINDS[kind][2] and run["refused"] == []
    assert run["layers"] == 2
    assert reports[0]["kept_bytes"] == 2 * run["bytes_a_layer"] > 0
    assert all(r["kept_bytes"] == 0 and r["runs"] == []
               for r in reports[2:])
    left = set(named["today"]) - wanted
    once = named["no remat"]         # (a name may be on several values)
    assert all(named["generous"][n] == once[n] for n in wanted)
    # (what the plan leaves is made again, or hangs on nothing any more)
    assert all(named["generous"][n] in (once[n], 2 * once[n]) for n in left)
    assert all(named["today"][n] == 2 * once[n] for n in wanted | left)
    for other in ("today", "no remat"):
        for a, b in zip(jax.tree.leaves(got["generous"]),
                        jax.tree.leaves(got[other])):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=5e-6)


def test_a_generous_plan_keeps_the_interpreted_kernels_residuals_too(
        monkeypatch):
    """With the flash kernel in the layer (interpreted here; the chip's
    dispatch) the plan keeps its ``out`` and ``lse`` as always and q, k,
    v beside them: the gradient holds each kernel once, the kernel's
    forward body is traced as often planned as not, and the numbers
    equal today's within the kernel tests' tolerance."""
    import importlib
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(mha, "flash_or_ref_attention",
                        functools.partial(fa.flash_attention,
                                          interpret=True))
    forwards = []
    real = fa._flash_forward
    monkeypatch.setattr(fa, "_flash_forward", lambda *a, **k: (
        forwards.append(1), real(*a, **k))[1])
    _, loss, x, stack = _two_layers("mha", length=128, head_dim=64)
    got, traced = {}, {}
    for how, memory in (("generous", ROOM), ("full", FULL), ("today", None)):
        _device(monkeypatch, memory)
        reports = []
        fn = _planned(loss, reports)
        jax.clear_caches()       # (the kernel's wrapper is a ``jax.jit``)
        del forwards[:]
        text = str(jax.make_jaxpr(fn)(x, stack))
        traced[how] = len(forwards)
        got[how] = jax.jit(fn)(x, stack)
        assert text.count("name=flash_attention_fwd") == 1
        assert text.count("name=flash_attention_bwd") == 1
        kept = set(reports[0]["runs"][0]["names"]) if reports[0]["runs"] \
            else set()
        assert (kept >= {"attn_q", "attn_k", "attn_v", "mid_residual"}) \
            == (how == "generous")
    assert traced["generous"] == traced["full"] == traced["today"] > 0
    for other in ("full", "today"):
        for a, b in zip(jax.tree.leaves(got["generous"]),
                        jax.tree.leaves(got[other])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def _survey_of(kind, layers=1, **more):
    """The survey of one layer of the kind, from its forward jaxpr."""
    cfg, _, x, stack = _two_layers(kind, **more)
    run = KINDS[kind][0]
    positions = jnp.zeros(x.shape[:2], jnp.int32)
    one = jax.tree.map(lambda a: a[0], stack)
    jaxpr = jax.make_jaxpr(lambda x, lp: transformer.apply_layer(
        x, lp, positions, cfg, kind=run, index=3,
        shared=SLOTS.get(kind, dict)())[:2])(x, one)
    return remat.survey(jaxpr.jaxpr, layers, run, x.size * 4)


def _surveys():
    """Two runs' surveys at sizes where bytes differ by name: the dense
    kind over 3 layers and the expert kind over 2."""
    return [_survey_of("mha", 3), _survey_of("moe-shared", 2)]


def test_no_budget_is_exactly_todays_two_names(monkeypatch):
    """Budget 0, none or negative (a step that fills the device
    already): every run keeps the kernels' own names (``BASE_NAMES``: the
    flash kernel's two and the delta rule's two), nothing else, and no
    candidate is even ordered; a device that reports no limit is not
    even planned for: its policy is ``save_only_these_names`` of
    those."""
    surveys = _surveys()
    for budget in (0, None, -5):
        names, report = remat.make_plan(surveys, budget)
        assert names == [(), ()]
        assert report["kept_bytes"] == 0
        assert all(r["names"] == [] for r in report["runs"])
    assert remat.no_plan() == {"budget_bytes": None, "kept_bytes": 0,
                               "runs": []}
    assert remat.BASE_NAMES == RESIDUAL_NAMES + gated_delta.RESIDUAL_NAMES \
        + ssd.RESIDUAL_NAMES + kda.RESIDUAL_NAMES
    assert len(set(remat.BASE_NAMES)) == 8
    assert remat.device_memory() is None
    policy = remat.policy(("mha", "dense"))
    assert not isinstance(policy, remat.Keeps)
    assert "save_only_these_names" in policy.__qualname__
    keeps = remat.Keeps(("mha", "dense"), 1)
    assert keeps.names == remat.BASE_NAMES
    keeps.keep(["attn_v"])
    assert keeps.names == remat.BASE_NAMES + ("attn_v",)


def _interpreted_kernels(monkeypatch):
    """The flash kernel and the delta rule's pair in the layers, as the
    chip's dispatch has them, interpreted."""
    import importlib
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(mha, "flash_or_ref_attention",
                        functools.partial(fa.flash_attention,
                                          interpret=True))
    monkeypatch.setattr(gated_delta, "gated_delta_rule", functools.partial(
        gated_delta.gated_delta_rule, use_pallas=True, interpret=True))


@pytest.mark.parametrize("kind,more,kernels,names", [
    ("mha", dict(length=128, head_dim=64),
     ["flash_attention_bwd", "flash_attention_fwd"], RESIDUAL_NAMES),
    ("gdn", dict(length=128),
     ["gated_delta_bwd", "gated_delta_fwd"], gated_delta.RESIDUAL_NAMES)])
def test_a_layer_saves_its_own_kernels_names_and_runs_each_kernel_once(
        kind, more, kernels, names, monkeypatch):
    """Of ``BASE_NAMES`` a layer's jaxpr holds those of the kernel it
    runs and no other: a layer without a delta rule saves exactly the
    flash kernel's two arrays, a delta layer the rule's two.  Under
    today's policy (no device to ask) and under a plan with no room the
    gradient of the two-layer scan holds each kernel once -- the forward
    scan's call; the backward scan runs the backward kernel alone -- and
    its numbers are those without remat."""
    _interpreted_kernels(monkeypatch)
    got = {}
    for how, memory in (("today", None), ("full", FULL), ("no remat", None)):
        _, loss, x, stack = _two_layers(kind, remat_on=how != "no remat",
                                        **more)
        _device(monkeypatch, memory)
        fn = _planned(loss)
        jaxpr = jax.make_jaxpr(fn)(x, stack).jaxpr
        assert {n for n in _names_in(jaxpr) if n in remat.BASE_NAMES} \
            == set(names)
        text = str(jaxpr)
        assert sorted(re.findall(r"name=(\w+_(?:fwd|bwd))\b", text)) \
            == kernels
        got[how] = jax.jit(fn)(x, stack)
    for other in ("today", "full"):
        for a, b in zip(jax.tree.leaves(got[other]),
                        jax.tree.leaves(got["no remat"])):
            assert bool(jnp.all(jnp.isfinite(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)


def test_a_growing_budget_keeps_a_growing_prefix_of_the_worth_order():
    """The order is by work avoided per byte, each candidate given those
    ahead of it, and never rises; a budget keeps the longest prefix that
    fits it and not a byte more; what follows the cut is reported as
    refused, with its bytes."""
    surveys = _surveys()
    order = remat.worth_order(surveys)
    assert len(order) > 8
    worth = [c.work / c.bytes for c in order]
    assert all(w > 0 for w in worth)
    # (a candidate ahead may raise one behind it -- two halves of one
    # product -- but never above itself)
    assert all(b <= a * (1 + 1e-9) or order[i].run != order[i + 1].run
               for i, (a, b) in enumerate(zip(worth, worth[1:])))
    for c in order:
        s = surveys[c.run]
        assert c.bytes == s.names[c.name] * s.layers
    sums = np.cumsum([c.bytes for c in order])
    before = -1
    for budget in [1, int(sums[0]) - 1, int(sums[0]), int(sums[3]),
                   int(sums[3]) + 1, int(sums[-1]), 1 << 40]:
        names, report = remat.make_plan(surveys, budget)
        n = int(np.searchsorted(sums, budget, side="right"))
        kept = [(r, name) for r, run in enumerate(report["runs"])
                for name in run["names"]]
        assert sorted(kept) == sorted((c.run, c.name) for c in order[:n])
        assert kept == [(r, name) for r, run in enumerate(names)
                        for name in run]
        assert report["kept_bytes"] == (sums[n - 1] if n else 0) <= budget
        refused = [(r, name, b) for r, run in enumerate(report["runs"])
                   for name, b in run["refused"]]
        assert sorted(refused) == sorted(
            (c.run, c.name, c.bytes) for c in order[n:])
        assert n >= before
        before = n
    assert before == len(order)


def test_bytes_are_counted_a_device_under_a_dp_sp_mesh(monkeypatch):
    """A named value's bytes on one device are its bytes over the
    devices that split the token axes: the survey divides by ``dp x
    sp`` of the mesh the run was given (``tp`` is left out: it does not
    split every candidate)."""
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    whole = _survey_of("mha")
    cfg, loss, x, stack = _two_layers("mha")
    mesh = build_mesh(MeshConfig(dp=2, sp=2), devices=jax.devices()[:4])
    positions = jnp.zeros((2, 32), jnp.int32)

    def under_mesh(x, stack):
        return jnp.sum(run_stack(x, stack, ("mha", "dense"), positions, cfg,
                                 mesh)[0] ** 2)

    _device(monkeypatch, ROOM)
    reports = []
    with mesh:
        jax.make_jaxpr(_planned(under_mesh, reports, mesh))(x, stack)
    jax.make_jaxpr(_planned(loss, reports))(x, stack)
    (shared,), (alone,) = (r["runs"] for r in reports)
    assert shared["names"] == alone["names"]
    assert shared["bytes_a_layer"] * 4 == alone["bytes_a_layer"] == sum(
        whole.names[n] for n in alone["names"])


def test_the_budget_is_counted_from_the_device(monkeypatch):
    """Where the device reports no limit (this CPU) nothing is planned;
    else the budget is ``SAFETY`` of the limit less what is resident
    less what the step needs whatever is kept (``step_bytes``: the
    stacks, and the larger of the head and of the gradients beside one
    layer's backward), and the objective is traced once all the same."""
    _, loss, x, stack = _two_layers("mha")
    traced = []

    def counting(x, stack):
        traced.append(1)
        return loss(x, stack)

    reports = []
    jax.make_jaxpr(_planned(counting, reports))(x, stack)
    assert reports.pop() == remat.no_plan() and len(traced) == 1
    limit, in_use = 1_330_000, 1_000_000
    _device(monkeypatch, (limit, in_use))
    jax.make_jaxpr(_planned(counting, reports))(x, stack)
    assert len(traced) == 2
    report = reports.pop()
    assert (report["bytes_limit"], report["bytes_in_use"]) == (limit, in_use)
    (run,) = report["runs"]
    # two layers' inputs at the least, and the gradients
    grads = sum(a.size * 4 for a in jax.tree.leaves((x, stack)))
    assert report["step_bytes"] > 2 * x.size * 4 + grads
    assert report["budget_bytes"] == int(remat.SAFETY * (
        limit - in_use - report["step_bytes"]))
    assert 0 < report["kept_bytes"] <= report["budget_bytes"]
    assert run["names"] and run["refused"]
    assert 0 < report["plan_seconds"] < 5 and report["trace_seconds"] > 0
    # a device that is full already: today's plan
    _device(monkeypatch, FULL)
    jax.make_jaxpr(_planned(counting, reports))(x, stack)
    report = reports.pop()
    assert report["budget_bytes"] < 0 and report["kept_bytes"] == 0
    assert report["runs"][0]["names"] == []


def test_a_period_keeps_its_runs_stacks_every_repeat(monkeypatch):
    """A run inside a period is the body of the scan over the repeats:
    its stacks are kept ``repeats`` times over, and the plan, which
    reads the scans' lengths from the step's jaxpr, counts them so."""
    from ray_tpu.models.transformer import init_params, loss_fn
    cfg = TransformerConfig(**{**_BASE, "layer_pattern": (
        ((("mha", "dense", 2), ("mha", "dense", 1)), 3),)})
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    _device(monkeypatch, ROOM)
    plans = {}
    jax.make_jaxpr(lambda p: remat.value_and_grad(
        lambda p: (loss_fn(p, batch, cfg), {}), p, None, plans)[0])(params)
    (_, report), = plans.values()
    assert sorted(r["layers"] for r in report["runs"]) == [3, 6]
    assert report["kept_bytes"] == sum(
        r["layers"] * r["bytes_a_layer"] for r in report["runs"])
    # the scan stacks a layer's input whatever is kept
    carry = 2 * 32 * 32 * 4
    assert report["step_bytes"] >= 9 * carry


def test_a_name_has_to_spare_more_than_keeping_it_costs(monkeypatch):
    """At a cell's widths (2,048 columns, bfloat16, 16,384 tokens; shapes
    alone, nothing is computed) the dense layer's products are worth some
    ten bytes moved a byte kept and every one is ordered; at 32 columns a
    product alone is no longer worth its bytes (the FFN's two, v), only
    what has several passes behind it."""
    monkeypatch.undo()
    assert remat._KEPT_BYTE_MOVES >= 2.0
    for width, wanted in ((2048, {"mid_residual", "attn_q", "attn_k",
                                  "attn_v", "ffn_gate", "ffn_up"}),
                          (32, {"mid_residual", "attn_q", "attn_k"})):
        cfg = TransformerConfig(**{**_BASE, "d_model": width, "n_heads": 16,
                                   "d_ff": 5504 * width // 2048,
                                   "dtype": jnp.bfloat16})
        stack = jax.eval_shape(
            lambda: init_stack(jax.random.PRNGKey(0), cfg, "mha", "dense", 1))
        x = jax.ShapeDtypeStruct((4, 4096, width), jnp.bfloat16)
        positions = jnp.zeros((4, 4096), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda x, lp: apply_layer(x, lp, positions, cfg))(
                x, jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                    a.shape[1:], a.dtype), stack))
        order = remat.worth_order([remat.survey(jaxpr.jaxpr)])
        assert {c.name for c in order} == wanted
        assert all(c.work / c.bytes > remat._KEPT_BYTE_MOVES for c in order)


def _the_cells_delta_layer(monkeypatch):
    """The forward jaxpr of one delta layer at the hybrid cell's widths,
    traced as on a TPU (the rule and the convolution are their kernel
    pairs: shapes alone, nothing is compiled)."""
    monkeypatch.undo()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = TransformerConfig(**{
        **_BASE, "d_model": 2048, "n_heads": 16, "d_ff": 512,
        "dtype": jnp.bfloat16, "layer_pattern": (("gdn", "dense", 1),),
        "gdn": GDNConfig(16, 32, 128, 128)})
    stack = jax.eval_shape(
        lambda: init_stack(jax.random.PRNGKey(0), cfg, "gdn", "dense", 1))
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16)
    positions = jnp.zeros((2, 8192), jnp.int32)
    return jax.make_jaxpr(
        lambda x, lp: apply_layer(x, lp, positions, cfg))(
            x, jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape[1:], a.dtype), stack))


def test_the_convolutions_output_is_not_worth_its_bytes_at_the_cell(
        monkeypatch):
    """At the hybrid cell's widths the delta layer's ``gdn_mixed`` -- 537
    MB of float32 a layer -- stays out of the order: keeping it would
    spare one pass of the forward kernel, which takes ``qkvz`` twice (a
    tile and the positions before it) and reads it once, so it is priced
    once; ``gdn_qkvz``, a product, is in."""
    jaxpr = _the_cells_delta_layer(monkeypatch)
    assert "causal_conv_fwd" in str(jaxpr)
    survey = remat.survey(jaxpr.jaxpr)
    assert survey.names["gdn_mixed"] == 2 * 8192 * 16 * 512 * 4
    ordered = {c.name for c in remat.worth_order([survey])}
    assert "gdn_qkvz" in ordered and "gdn_mixed" not in ordered


def test_the_rules_two_names_are_stack_bytes_and_no_candidate_at_the_cell(
        monkeypatch):
    """At the hybrid cell's widths the rule's ``o`` (134 MB a layer) and
    the state entering each grid step of 8 chunks (67 MB) are counted in
    what a delta layer's run stacks whatever the plan, beside its carry;
    no plan is offered them; and with them kept the forward kernel is not
    among what the backward makes again, while what feeds its operands
    (the convolution's kernel) still is."""
    jaxpr = _the_cells_delta_layer(monkeypatch)
    assert "gated_delta_fwd" in str(jaxpr)
    carry = 2 * 8192 * 2048 * 2
    survey = remat.survey(jaxpr.jaxpr, layers=3, carry_bytes=carry)
    o, steps = 2 * 32 * 8192 * 128 * 2, 2 * 32 * (8192 // 64 // 8) * 128 \
        * 128 * 4
    assert (o, steps) == (134217728, 67108864)
    assert survey.stack_bytes == carry + o + steps
    assert not set(survey.names) & set(remat.BASE_NAMES)
    assert {c.name for c in remat.worth_order([survey])} \
        >= {"gdn_qkvz", "gdn_ba"}
    for kept in ((), tuple(survey.names)):
        again = survey.recomputed(kept)
        kernels = [e for e in again if e.prim == "pallas_call"]
        # (the rule's kernel is the one with a [Dk, Dv] state among its
        # results)
        assert not any(v.bytes == steps for e in kernels for v in e.outs)
        assert bool(kernels) == (kept == ())
    # under a dp x sp mesh: a device's share
    shared = remat.survey(jaxpr.jaxpr, carry_bytes=carry, shards=2)
    assert shared.stack_bytes == (carry + o + steps) // 2


def test_every_named_cut_point_is_a_candidate_some_survey_sees():
    """Every ``checkpoint_name`` literal in ``models/`` and ``ops/`` is
    a candidate in the survey of some kind of layer here (the kernels'
    own are ``BASE_NAMES``, not literals): a name that no survey sees is
    never kept."""
    literals = set()
    for part in ("models", "ops"):
        folder = os.path.join(ROOT, "ray_tpu", part)
        for name in sorted(os.listdir(folder)):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name)) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call) and getattr(
                        node.func, "id", "") == "checkpoint_name"
                        and isinstance(node.args[1], ast.Constant)):
                    literals.add(node.args[1].value)
    assert len(literals) >= 20
    seen = set()
    for kind in KINDS:
        seen |= set(_survey_of(kind).names)
    assert literals - seen == set()
    assert not seen & set(remat.BASE_NAMES)


def test_two_mha_runs_of_unequal_shapes_are_two_surveys(monkeypatch):
    """The step whose ``mha`` runs differ (9 heads under a window, 6
    over everything before, a dense and an expert FFN): a survey a run,
    each with its own bytes under the names the runs share, one order
    over all of them, and a budget that holds a part of it refuses the
    rest run by run."""
    seen, real = [], remat.survey

    def kept(*args, **kwargs):
        seen.append(real(*args, **kwargs))
        return seen[-1]

    monkeypatch.setattr(remat, "survey", kept)
    _device(monkeypatch, ROOM)
    step, state, batch = _tiny_step("windowed")
    step.lower(state, batch)
    by_kind = {"+".join(s.kind): s for s in seen}
    assert sorted(by_kind) == RUNS["windowed"]
    dense, full, window = (by_kind[k] for k in RUNS["windowed"])
    assert (dense.layers, full.layers, window.layers) == (1, 1, 3)
    # q as it enters the kernel: 2 rows x 32 positions x heads x 16, f32
    assert window.names["attn_q"] == 2 * 32 * 9 * 16 * 4
    assert full.names["attn_q"] == dense.names["attn_q"] == 2 * 32 * 6 * 16 * 4
    assert window.names["attn_head_gate"] == 2 * 32 * 9 * 4
    assert window.names["attn_k"] == full.names["attn_k"]
    assert "ffn_gate" in dense.names and "moe_scores" not in dense.names
    plan = step._kept
    assert [r["kind"] for r in plan["runs"]] == [
        "+".join(s.kind) for s in seen]
    assert all("attn_q" in r["names"] and r["refused"] == []
               for r in plan["runs"])
    # half the room: some of one order is refused, by the run it is of
    used = plan["bytes_limit"] - plan["budget_bytes"] / remat.SAFETY
    _device(monkeypatch, (int(used + plan["kept_bytes"] / 2 / remat.SAFETY),
                          0))
    step, state, batch = _tiny_step("windowed")
    step.lower(state, batch)
    tight = step._kept
    assert 0 < tight["kept_bytes"] <= tight["budget_bytes"] \
        < plan["kept_bytes"]
    assert any(r["refused"] for r in tight["runs"])
    for r, whole in zip(tight["runs"], plan["runs"]):
        assert {n for n, _ in r["refused"]} | set(r["names"]) == \
            set(whole["names"])
