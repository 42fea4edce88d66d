"""Layers that are each one sublayer, as Nemotron-H lays them out: Mamba-2
mixers (``models/mamba2.py`` over ``ops/ssd.py``), attention without
positional encoding, and expert layers of gate-less squared-ReLU experts
in a latent beside a full-width shared expert with a sigmoid router and
its correction bias (``models/moe.py``); a mixer with no FFN after it is
a layer alone (FFN kind ``none``).  At a tiny size against the
benchmark's plain reference (``benchmarks/reference/mamba2_latent_moe.py``),
which runs the state-space rule token by token."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models.mamba2 import gated_group_norm  # noqa: E402
from ray_tpu.models.transformer import (  # noqa: E402
    apply_layer, init_params, make_train_state, make_train_step)

# The published layers 0-10, MEMEMEM*EME, at a tiny width: 4 Mamba-2
# heads of 8 over 2 groups of 16 states (chunks of 16 over rows of 32:
# two chunks a row), 4 query heads of 8 over 2 K/V heads, 8 experts of
# 24 in a latent of 16 of which this rank holds 4 (experts 4-7), 3 a
# token times 5, a shared expert of 32.
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nemotron-3-super-120b-a12b-train.json")) as f:
    CONFIG = dict(
        json.load(f), hidden_size=48, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, mamba_num_heads=4,
        mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=16,
        n_routed_experts=8, n_routed_experts_held=4, experts_held_first=4,
        num_experts_per_tok=3, moe_intermediate_size=24, moe_latent_size=16,
        moe_shared_expert_intermediate_size=32, vocab_size=128,
        dtype="float32")
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 2, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
CELL = {"check": {"steps": 2}}
# float32 on both sides, the reference following the program's experts:
# summation order and the chunked rule against the recurrence (read:
# grad1 3e-7, change 4e-5).  The weakest control here (fp8) reads 300
# times the first.
LIMITS = {"grad1_norm_gap": 1e-4, "change_norm_gap": 1e-3,
          "routing_gap": 1e-5}
LOSS_GAP = 1e-5


def _driver():
    from benchmarks.drivers import trainer_mamba2_moe_steps as driver
    return driver


def _cfg(**changes):
    driver = _driver()
    kwargs = driver._model_kwargs(CONFIG, TRAFFIC["seq_len"])
    return driver.transformer_config(dict(kwargs, **changes), jnp.float32)


def _batches(seed):
    from benchmarks.harness import traffic
    return list(traffic.generate(TRAFFIC, seed, vocab_size=128))


def _program(seed, batches, dtype=jnp.float32):
    """Two steps of ``make_train_step`` from the seed's weights -> what
    the reference returns.  ``dtype``: what the matrices are rounded to
    before every step's products."""
    from benchmarks.drivers.trainer_steps import _adam_mu
    from benchmarks.harness import mamba2_moe_weights
    driver = _driver()
    cfg = _cfg()
    state, tx = make_train_state(
        jax.random.PRNGKey(0), cfg,
        learning_rate=CONFIG["optimizer"]["learning_rate"])
    start = mamba2_moe_weights.make_decoder(seed, CONFIG, jnp.float32)
    assert jax.tree.map(jnp.shape, start) == jax.tree.map(
        jnp.shape, state["params"])
    state["params"] = start
    step = make_train_step(cfg, tx)
    if dtype != jnp.float32:
        def rounded(a):
            return a.astype(dtype).astype(a.dtype) if a.ndim > 2 else a

        def step(state, batch, inner=step):
            return inner(dict(state, params=jax.tree.map(
                rounded, state["params"])), batch)
    out = {"losses": [], "metrics": [], "choices": []}
    for i, batch in enumerate(batches):
        state, metrics = step(state, {"tokens": jnp.asarray(batch)})
        out["losses"].append(float(metrics["loss"]))
        out["choices"].append(np.asarray(metrics.pop("moe_choices")))
        out["metrics"].append({k: float(v) for k, v in metrics.items()})
        if i == 0:
            out["grad1_norm"] = {
                k: np.asarray(v, np.float64) / (1.0 - 0.9) for k, v in
                driver.leaf_norms(_adam_mu(state["opt"])).items()}
    again = mamba2_moe_weights.make_decoder(seed, CONFIG, jnp.float32)
    out["change_norm"] = {k: np.asarray(v, np.float64) for k, v in
                          driver.leaf_norms(jax.tree.map(
                              lambda a, b: a - b, state["params"],
                              again)).items()}
    out["moe_bias"] = np.asarray(state["moe_bias"])
    return out


def _numbers(prog, ref):
    from benchmarks.harness import compare
    return dict(compare.train_numbers(prog, ref),
                routing_gap=ref["routing_gap"])


def test_program_matches_the_plain_reference_and_the_controls_do_not():
    """The whole loss, the first gradient leaf by leaf, the parameters'
    change over two AdamW steps and the routers' bias after them; each
    control in the reference's place reads false."""
    from benchmarks.harness import compare
    driver = _driver()
    seed = 2**31 + 23
    batches = _batches(seed)
    prog = _program(seed, batches)
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=prog["choices"])
    # 5 expert layers, 2 rows x 32 positions, 3 choices
    assert prog["choices"][0].shape == (5, 2, 32, 3)
    assert max(compare.loss_gaps(prog, ref)) <= LOSS_GAP
    correct, compared = compare.judge(_numbers(prog, ref), LIMITS)
    assert correct, compared
    np.testing.assert_array_equal(prog["moe_bias"], ref["moe_bias"])
    # moved after each of the two steps, by the rate
    assert np.abs(prog["moe_bias"]).max() == pytest.approx(
        2 * CONFIG["router_bias_update_rate"])
    # every leaf of the four runs is among the compared, a norm a layer
    leaves = set(prog["grad1_norm"])
    assert {"layers.0.mamba2.w_xbc", "layers.0.mamba2.conv_b",
            "layers.0.mamba2.A_log", "layers.0.mamba2.D",
            "layers.0.moe.w_down", "layers.0.moe.w_up", "layers.0.moe.ws1",
            "layers.1.mamba2.norm", "layers.2.wq", "layers.2.moe.w1",
            "layers.3.ln2", "embed", "lm_head"} <= leaves
    assert "layers.1.ln2" not in leaves
    assert prog["grad1_norm"]["layers.0.mamba2.w_z"].shape == (3,)
    for metrics in prog["metrics"]:
        assert metrics["moe_dropped_choices"] == 0.0
        assert metrics["ssd_fallback_passes"] == 1.0      # off the TPU
        assert 0.001 <= metrics["ssd_dt_mean"] <= 0.1
    for how in (dict(precision="fp8"), dict(decay=False), dict(skip=False),
                dict(norm_groups=1), dict(act="relu"), dict(latent=False)):
        control = driver.follow_reference(CELL, CONFIG, seed, batches,
                                          choices=prog["choices"], **how)
        correct, compared = compare.judge(_numbers(prog, control), LIMITS)
        assert not correct, (how, compared)


def test_matrices_in_a_lower_precision_fail_the_comparison():
    from benchmarks.harness import compare
    driver = _driver()
    seed = 78
    batches = _batches(seed)
    prog = _program(seed, batches, jnp.bfloat16)
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=prog["choices"])
    correct, compared = compare.judge(_numbers(prog, ref), LIMITS)
    assert not correct, compared
    assert compared["grad1_norm_gap"]["value"] > 5 * LIMITS["grad1_norm_gap"]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    seen = ["benchmarks/reference/mamba2_latent_moe.py"]
    for path in seen:
        tree = ast.parse(open(os.path.join(ROOT, path)).read())
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            for name in names:
                assert not name.startswith("ray_tpu"), (path, name)
                if name.startswith("benchmarks."):
                    inner = name.replace(".", "/") + ".py"
                    if inner not in seen:
                        seen.append(inner)
    assert len(seen) > 3


def test_the_pattern_is_built_from_the_published_string():
    """``MEMEMEM*EME``: a mixer takes the expert layer after it, the
    ``M`` before ``*`` is a layer alone; the tree is four runs' stacks
    and the alone layer has one norm and no FFN leaf."""
    from benchmarks.harness import mamba2_moe_weights
    assert CONFIG["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    cfg = _cfg()
    assert cfg.layer_pattern == (("mamba2", "moe", 3), ("mamba2", "none", 1),
                                 ("mha", "moe", 1), ("mamba2", "moe", 1))
    assert cfg.n_layers == 6 and cfg.moe_layers == 5 and cfg.rope == "none"
    plan = mamba2_moe_weights.layer_plan(CONFIG)
    assert [e["index"] for e in plan] == [0, 2, 4, 6, 7, 9]
    params = init_params(jax.random.PRNGKey(0), cfg)
    runs = params["layers"]
    assert len(runs) == 4
    assert set(runs[1]) == {"ln1", "mamba2"}
    assert set(runs[0]) == {"ln1", "ln2", "mamba2", "moe"}
    assert set(runs[2]) == {"ln1", "ln2", "wq", "wk", "wv", "wo", "moe"}
    assert set(runs[0]["moe"]) == {"wr", "w1", "w2", "w_down", "w_up",
                                   "ws1", "ws2"}
    assert runs[0]["moe"]["w1"].shape == (3, 4, 16, 24)
    assert runs[0]["moe"]["w_down"].shape == (3, 48, 16)
    assert runs[0]["mamba2"]["w_xbc"].shape == (3, 48, 4 * 8 + 2 * 2 * 16)
    # the program's tree is the benchmark's, leaf for leaf
    want = jax.tree.map(jnp.shape, params)
    have = jax.tree.map(jnp.shape, mamba2_moe_weights.make_decoder(
        5, CONFIG, jnp.float32))
    assert want == have
    assert sum(a.size for a in jax.tree.leaves(params)) == \
        mamba2_moe_weights.parameter_count(CONFIG)


def test_a_layer_of_kind_none_is_its_mixer_alone():
    """``("mamba2", "none")``: ``x + mixer(rmsnorm(x; ln1))`` and nothing
    after, whatever an FFN's leaves would say; a ``"dense"`` FFN on the
    same mixer adds to it."""
    cfg = _cfg()
    stack = init_params(jax.random.PRNGKey(2), cfg)["layers"][1]
    lp = jax.tree.map(lambda a: a[0], stack)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 48), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    alone, counted, _ = apply_layer(x, lp, positions, cfg,
                                    kind=("mamba2", "none"))
    from ray_tpu.models.common import LayerCall, model_norm
    from ray_tpu.models.mamba2 import MAMBA2
    mixed, _, _ = MAMBA2.apply(model_norm(x, lp, "ln1", cfg), lp,
                               LayerCall(cfg, "mamba2", {}, positions))
    np.testing.assert_allclose(alone, x + mixed, rtol=1e-6, atol=1e-6)
    assert set(counted) == {"ssd_fallback_passes", "ssd_dt_mean"}
    with_ffn = dict(lp, ln2=jnp.ones((48,)), w1=jnp.ones((48, 8)),
                    w3=jnp.ones((48, 8)), w2=jnp.ones((8, 48)))
    dense, _, _ = apply_layer(x, with_ffn, positions, cfg,
                              kind=("mamba2", "dense"))
    assert float(jnp.max(jnp.abs(dense - alone))) > 1e-2


def test_the_norm_after_the_gate_is_grouped():
    """``MambaRMSNormGated``: the gate first, then each group of columns
    to unit root mean square on its own; one group for the whole row is
    another function."""
    y = jax.random.normal(jax.random.PRNGKey(4), (3, 32))
    z = jax.random.normal(jax.random.PRNGKey(5), (3, 32))
    w = 1.0 + jax.random.uniform(jax.random.PRNGKey(6), (32,))
    got = gated_group_norm(y, z, w, 4, 1e-5)
    gated = (y * jax.nn.silu(z)).reshape(3, 4, 8)
    want = gated / jnp.sqrt(jnp.mean(gated ** 2, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(3, 32) * w, rtol=1e-5)
    one = gated_group_norm(y, z, w, 1, 1e-5)
    assert float(jnp.max(jnp.abs(one - got))) > 1e-2


def test_the_shares_add_up_to_the_uncut_layer():
    """The held shares 0-3 and 4-7 of an 8-expert latent layer (each
    projecting its own sum up), the shared expert counted once, sum to
    what the plain reference gives for the whole layer with all eight
    experts: the router's bias, the scale and both latent projections
    included."""
    from benchmarks.reference import mamba2_latent_moe as reference
    cfg = _cfg(moe_experts_held=None)
    lp = jax.tree.map(lambda a: a[0], init_params(
        jax.random.PRNGKey(7), cfg)["layers"][0]["moe"])
    lp["wr"] = lp["wr"] * 40.0           # a router that spreads
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (8,))
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 48), jnp.float32)
    total = moe.shared_expert(h, lp)
    seen = []
    for first in (0, 4):
        share = dict(lp, bias=bias, **{k: lp[k][first:first + 4]
                                       for k in ("w1", "w2")})
        y, stats = moe.moe_ffn(h, share, 3, True, held=(first, 4),
                               scoring="sigmoid", route_scale=5.0)
        assert int(stats["dropped_choices"]) == 0
        total = total + y
        seen.append(int(stats["held_choices"]))
    assert sum(seen) == 2 * 32 * 3 and min(seen) > 0
    hp = {"top_k": 3, "first": 0, "route_scale": 5.0, "act": "relu2",
          "latent": True}
    flat = {"moe." + k: v for k, v in lp.items()}
    for r in range(2):
        want, _, _ = reference._experts(flat, h[r], bias, hp, "float32",
                                        None)
        assert float(jnp.max(jnp.abs(total[r] - want))) <= 2e-5
        # the experts are squared ReLU: with ReLU the layer differs
        relu, _, _ = reference._experts(flat, h[r], bias, dict(hp, act="relu"),
                                        "float32", None)
        assert float(jnp.max(jnp.abs(relu - want))) > 1e-3


def test_the_swiglu_form_is_the_tree_and_output_it_was():
    """No ``moe_act`` / ``moe_latent`` said: the leaves of a SwiGLU
    expert layer are the ones they always were (``w3``, ``ws3``, no
    latent pair) and the layer's output is ``silu(x w1) . x w3`` through
    ``w2``, summed over the held experts with their gates."""
    from ray_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, d_ff=16,
                            moe_experts=4, moe_top_k=2, moe_shared_width=16,
                            dtype=jnp.float32)
    lp = jax.tree.map(lambda a: a[0], init_params(
        jax.random.PRNGKey(1), cfg)["layers"]["moe"])
    assert set(lp) == {"wr", "w1", "w3", "w2", "ws1", "ws3", "ws2"}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 32))
    y, _ = moe.moe_ffn(h, lp, 2, True)
    probs = jax.nn.softmax(h[0] @ lp["wr"], -1)
    gate, chosen = jax.lax.top_k(probs, 2)
    gate = gate / gate.sum(-1, keepdims=True)
    want = sum(
        jnp.sum(jnp.where(chosen == e, gate, 0.0), -1)[:, None]
        * ((jax.nn.silu(h[0] @ lp["w1"][e]) * (h[0] @ lp["w3"][e]))
           @ lp["w2"][e]) for e in range(4))
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)
