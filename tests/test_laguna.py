"""Two kinds of ``mha`` run in one model (``models/transformer.py``): a
window and everything before, with different head counts, rotary tables
(one of them YaRN's) and masks, a head-wise output gate, a leading dense
layer and expert layers with a scaled softmax router beside a shared
expert (``models/moe.py``), at a tiny size against the benchmark's plain
reference (``benchmarks/reference/swa_gqa_moe.py``)."""

import dataclasses
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from ray_tpu.models import moe  # noqa: E402
from ray_tpu.models.transformer import (  # noqa: E402
    RopeTable, TransformerConfig, init_params, loss_and_counters,
    make_train_state, make_train_step, param_specs, run_options)

# Layer 0 (full attention, dense) and one period of 3 window layers and a
# full one: 9 | 6 query heads of 16 over 3 K/V heads, a window of 8 over
# rows of 32, rotary on all 16 columns (theta 100) | on the first 8 under
# YaRN first trained for 16 positions; 8 experts of which this rank holds
# 4 (experts 4-7), 3 a token times 2.5, a shared expert.
CONFIG = {
    "reference": "swa_gqa_moe", "hidden_size": 48, "intermediate_size": 64,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_attention_heads": 6, "num_key_value_heads": 3, "head_dim": 16,
    "num_hidden_layers": 5, "vocab_size": 128, "sliding_window": 8,
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "num_attention_heads_per_layer": [6, 9, 9, 9, 6],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 5000, "rope_type": "yarn", "factor": 8,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2079441541679836,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 100,
                              "partial_rotary_factor": 1}},
    "gating": "per-head", "attention_bias": False,
    "moe_router_logit_softcapping": 0,
    "moe_apply_router_weight_on_input": False, "tie_word_embeddings": False,
    "num_experts": 8, "num_experts_per_tok": 3, "num_experts_held": 4,
    "experts_held_first": 4, "norm_topk_prob": True,
    "moe_routed_scaling_factor": 2.5, "router_aux_loss_coef": 0.0,
    "dispatch_alike_tail": 0.01, "rms_norm_eps": 1e-6,
    "initializer_range": 0.02, "dtype": "float32", "remat": True,
    "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                  "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1},
}
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 2, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
CELL = {"check": {"steps": 2}}
# float32 on both sides, the reference following the program's experts:
# summation order alone.  The weakest control by these two (YaRN left
# out) reads 200 times the first.
LIMITS = {"grad1_norm_gap": 1e-4, "change_norm_gap": 1e-3,
          "routing_gap": 1e-5}
LOSS_GAP = 1e-5
WINDOW_RUN = "mha:heads=9,window=8,rope=local"
FULL_RUN = "mha:heads=6,rope=global"


def _driver():
    from benchmarks.drivers import trainer_swa_moe_steps as driver
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
    before every step's products (the norms and the router stay)."""
    from benchmarks.drivers.trainer_steps import _adam_mu
    from benchmarks.harness import swa_moe_weights
    driver = _driver()
    cfg = _cfg()
    state, tx = make_train_state(
        jax.random.PRNGKey(0), cfg,
        learning_rate=CONFIG["optimizer"]["learning_rate"])
    start = swa_moe_weights.make_decoder(seed, CONFIG, jnp.float32)
    assert jax.tree.map(jnp.shape, start) == jax.tree.map(
        jnp.shape, state["params"])
    assert np.array_equal(state["params"]["ln_f"], start["ln_f"])
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
    again = swa_moe_weights.make_decoder(seed, CONFIG, jnp.float32)
    out["change_norm"] = {k: np.asarray(v, np.float64) for k, v in
                          driver.leaf_norms(jax.tree.map(
                              lambda a, b: a - b, state["params"],
                              again)).items()}
    return out


def _numbers(prog, ref):
    from benchmarks.harness import compare
    return dict(compare.train_numbers(prog, ref),
                routing_gap=ref["routing_gap"])


def test_program_matches_the_plain_reference_and_the_controls_do_not():
    """The whole loss, the first gradient leaf by leaf and the
    parameters' change over two AdamW steps; each control in the
    reference's place reads false."""
    from benchmarks.harness import compare
    driver = _driver()
    seed = 2**31 + 11
    batches = _batches(seed)
    prog = _program(seed, batches)
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=prog["choices"])
    # 4 expert layers, 2 rows x 32 positions, 3 choices, in their order
    assert prog["choices"][0].shape == (4, 2, 32, 3)
    assert max(compare.loss_gaps(prog, ref)) <= LOSS_GAP
    correct, compared = compare.judge(_numbers(prog, ref), LIMITS)
    assert correct, compared
    # every leaf of all three kinds of layer is among the compared, a
    # norm a layer: the dense run's 1, the period's 3 and 1
    leaves = set(prog["grad1_norm"])
    assert {"layers.0.wq", "layers.0.wg", "layers.0.w1", "layers.1.0.wq",
            "layers.1.0.wg", "layers.1.0.moe.wr", "layers.1.0.moe.ws2",
            "layers.1.1.wg", "layers.1.1.moe.w1", "embed",
            "lm_head"} <= leaves
    assert prog["grad1_norm"]["layers.0.wq"].shape == (1,)
    assert prog["grad1_norm"]["layers.1.0.wo"].shape == (3,)
    assert prog["grad1_norm"]["layers.1.1.wk"].shape == (1,)
    for metrics in prog["metrics"]:
        assert metrics["moe_dropped_choices"] == 0.0
        assert 0 < metrics["moe_held_choices"] < 2 * 32 * 3
        assert 0.4 < metrics["attn_gate_mean"] < 0.6
        assert 0.4 < metrics["attn_window_gate_mean"] < 0.6
        assert metrics["attn_gate_mean"] != metrics["attn_window_gate_mean"]
    # each control against the program (the reference follows the
    # program's experts, as the cell's check does)
    for how in (dict(precision="fp8"), dict(window=0), dict(window=16),
                dict(window_heads=6), dict(attn_gate=False),
                dict(yarn=False), dict(rotary="all"),
                dict(route_scale=1.0), dict(shared=False)):
        control = driver.follow_reference(CELL, CONFIG, seed, batches,
                                          choices=prog["choices"], **how)
        correct, compared = compare.judge(_numbers(prog, control), LIMITS)
        assert not correct, (how, compared)


def test_matrices_in_a_lower_precision_fail_the_comparison():
    """The program with its matrices rounded to bfloat16 before every
    step, against the float32 reference at ``highest``."""
    from benchmarks.harness import compare
    driver = _driver()
    seed = 77
    batches = _batches(seed)
    prog = _program(seed, batches, jnp.bfloat16)
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=prog["choices"])
    correct, compared = compare.judge(_numbers(prog, ref), LIMITS)
    assert not correct, compared
    assert compared["grad1_norm_gap"]["value"] > 5 * LIMITS["grad1_norm_gap"]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    seen = ["benchmarks/reference/swa_gqa_moe.py"]
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


def _yarn_by_hand(width, theta, factor, first, beta_fast, beta_slow):
    """arXiv:2309.00071's blend, written out."""
    out = []

    def c(turns):
        return width * math.log(first / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), width - 1)
    for i in range(width // 2):
        f = theta ** (-2.0 * i / width)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return np.array(out), low, high


@pytest.mark.parametrize("width,theta,factor,first,fast,slow", [
    (64, 500_000.0, 128.0, 8192, 32.0, 1.0),     # the published table
    (8, 5000.0, 8.0, 16, 4.0, 1.0),              # the toy's
])
def test_the_yarn_table_is_the_formula(width, theta, factor, first, fast,
                                       slow):
    from benchmarks.reference.swa_gqa_moe import rotary_frequencies
    want, low, high = _yarn_by_hand(width, theta, factor, first, fast, slow)
    table = RopeTable(theta, width, factor, first, fast, slow, 1.5)
    got = np.asarray(table.frequencies(width))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    ref = rotary_frequencies({
        "rope_theta": theta, "rope_type": "yarn", "factor": factor,
        "original_max_position_embeddings": first, "beta_fast": fast,
        "beta_slow": slow}, width)
    np.testing.assert_allclose(ref, want, rtol=1e-6)
    if width == 64:
        assert (low, high) == (9, 18)
    # the fastest frequencies stay, the slowest are divided by the factor
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(
        theta ** (-2.0 * (width // 2 - 1) / width) / factor, rel=1e-6)
    # and the plain table is what it always was, bit for bit
    plain = RopeTable(theta).frequencies(width)
    half = width // 2
    assert np.array_equal(plain, jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half))
    with pytest.raises(ValueError, match="first trained"):
        RopeTable(theta, factor=4.0)


def test_rotary_by_table_scales_cos_and_sin():
    from ray_tpu.models.common import _rope
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 16))
    positions = jnp.arange(32)[None]
    table = RopeTable(5000.0, 8, 8.0, 16, 4.0, 1.0, 1.25)
    got = _rope(x, positions, table)
    # the last 8 columns pass; the first 8 are the unscaled table's
    # rotation times the factor
    assert np.array_equal(got[..., 8:], x[..., 8:])
    unscaled = _rope(x, positions, dataclasses.replace(
        table, attention_factor=1.0))
    np.testing.assert_allclose(got[..., :8], 1.25 * unscaled[..., :8],
                               rtol=1e-4, atol=1e-6)
    # a rotation keeps a pair's length
    np.testing.assert_allclose(
        jnp.sum(unscaled[..., :8] ** 2, -1), jnp.sum(x[..., :8] ** 2, -1),
        rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """The held shares 0-3 and 4-7 of an 8-expert layer, the shared
    expert counted once, sum to what the plain reference gives for the
    whole layer with all eight experts: the scaled gates included."""
    from benchmarks.reference import swa_gqa_moe as reference
    cfg = _cfg(moe_experts_held=None)
    lp = jax.tree.map(lambda a: a[0, 0], init_params(
        jax.random.PRNGKey(7), cfg)["layers"][1][0]["moe"])
    lp["wr"] = lp["wr"] * 40.0           # a router that spreads
    h = jax.random.normal(jax.random.PRNGKey(8), (2, 32, 48), jnp.float32)
    total = moe.shared_expert(h, lp)
    seen = []
    for first in (0, 4):
        share = dict(lp, **{k: lp[k][first:first + 4]
                            for k in ("w1", "w3", "w2")})
        y, stats = moe.moe_ffn(h, share, 3, True, held=(first, 4),
                               route_scale=2.5)
        assert int(stats["dropped_choices"]) == 0
        total = total + y
        seen.append(int(stats["held_choices"]))
    # every choice lands on exactly one share
    assert sum(seen) == 2 * 32 * 3 and min(seen) > 0
    hp = {"top_k": 3, "norm_topk": True, "first": 0, "route_scale": 2.5,
          "shared": True}
    flat = {"moe." + k: v for k, v in lp.items()}
    for r in range(2):
        want, _ = reference._experts(flat, h[r], hp, "float32", None)
        assert float(jnp.max(jnp.abs(total[r] - want))) <= 2e-5
        # the scale is in it: without, the routed part is 2.5 times less
        plain, _ = reference._experts(flat, h[r], dict(hp, route_scale=1.0),
                                      "float32", None)
        shared = moe.shared_expert(h, lp)[r]
        np.testing.assert_allclose(want - shared, 2.5 * (plain - shared),
                                   rtol=1e-4, atol=1e-6)


def test_the_softmax_router_scales_its_gates_and_one_leaves_them():
    cfg = _cfg(moe_experts_held=None)
    lp = jax.tree.map(lambda a: a[0, 0], init_params(
        jax.random.PRNGKey(3), cfg)["layers"][1][0]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(4), (1, 32, 48), jnp.float32)
    one, _ = moe.moe_ffn(h, lp, 3, True)
    scaled, _ = moe.moe_ffn(h, lp, 3, True, route_scale=2.5)
    np.testing.assert_allclose(scaled, 2.5 * one, rtol=1e-5, atol=1e-7)
    # a scale of one is the step it was: no multiplication is traced
    def muls(scale):
        jaxpr = jax.make_jaxpr(lambda h: moe.moe_ffn(
            h, lp, 3, True, route_scale=scale)[0])(h)
        return str(jaxpr).count(" mul ")
    assert muls(2.5) == muls(1.0) + 1


def test_a_run_that_says_nothing_is_the_tree_and_output_it_was():
    """``("mha", ffn, n)`` against the same run spelled out with the
    configuration's own heads, causal, under a table of its theta and
    rotary columns: the same leaves bit for bit from the same key, the
    same loss and gradients bit for bit."""
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                head_dim=8, d_ff=48, max_seq_len=16, rope_theta=5000.0,
                rotary_dim=4, qk_norm=True, dtype=jnp.float32)
    plain = TransformerConfig(n_layers=2, **base)
    said = TransformerConfig(
        layer_pattern=(("mha:heads=4,rope=own", "dense", 2),),
        rope_tables={"own": RopeTable(5000.0, 4)}, **base)
    assert plain.layer_pattern == (("mha", "dense", 2),)
    a = init_params(jax.random.PRNGKey(5), plain)
    b = init_params(jax.random.PRNGKey(5), said)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(x, y)
    assert set(a["layers"]) == {"ln1", "ln2", "k_norm", "q_norm", "w1", "w2",
                                "w3", "wk", "wo", "wq", "wv"}
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(6), (2, 17),
                                          0, 64)}

    def run(cfg, p):
        return jax.jit(jax.value_and_grad(
            lambda p: loss_and_counters(p, batch, cfg)[0]))(p)

    (loss_a, grad_a), (loss_b, grad_b) = run(plain, a), run(said, b)
    assert float(loss_a) == float(loss_b)
    for x, y in zip(jax.tree.leaves(grad_a), jax.tree.leaves(grad_b)):
        assert np.array_equal(x, y)
    # and what is traced for the silent run holds no new equation
    def text(cfg, p):
        # (less the address of the checkpoint policy's function)
        return re.sub(r"0x[0-9a-f]+", "", str(jax.make_jaxpr(
            lambda p: loss_and_counters(p, batch, cfg)[0])(p)))

    assert text(said, b) == text(plain, a)
    # the elementwise gate's tree is the one it was: a doubled wq, no wg
    gated = TransformerConfig(n_layers=1, attn_out_gate=True, **base)
    tree = init_params(jax.random.PRNGKey(5), gated)["layers"]
    assert tree["wq"].shape == (1, 32, 4, 16) and "wg" not in tree


def test_what_an_mha_run_says_sizes_its_leaves_and_its_specs():
    cfg = _cfg()
    assert cfg.layer_pattern == (
        (FULL_RUN, "dense", 1),
        (((WINDOW_RUN, "moe", 3), (FULL_RUN, "moe", 1)), 1))
    assert cfg.n_layers == 5 and cfg.moe_layers == 4
    assert run_options(WINDOW_RUN) == (
        "mha", {"heads": 9, "window": 8, "rope": "local"})
    params = init_params(jax.random.PRNGKey(0), cfg)
    dense, (window, full) = params["layers"]
    assert dense["wq"].shape == (1, 48, 6, 16)
    assert dense["wg"].shape == (1, 48, 6)
    assert dense["w1"].shape == (1, 48, 64)
    assert window["wq"].shape == (1, 3, 48, 9, 16)
    assert window["wo"].shape == (1, 3, 9, 16, 48)
    assert window["wg"].shape == (1, 3, 48, 9)
    assert window["wk"].shape == (1, 3, 48, 3, 16)
    assert full["wq"].shape == (1, 1, 48, 6, 16)
    assert full["moe"]["ws1"].shape == (1, 1, 48, 32)
    assert "wsg" not in full["moe"]
    specs = param_specs(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda s: 0, specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
    P = jax.sharding.PartitionSpec
    assert specs["layers"][0]["wg"] == P(None, None, "tp")
    assert specs["layers"][1][0]["wg"] == P(None, None, None, "tp")
    assert len(specs["layers"][1][0]["wq"]) == window["wq"].ndim


def test_runs_the_configuration_cannot_hold_are_refused_by_name():
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                head_dim=8, d_ff=48, max_seq_len=16)
    with pytest.raises(ValueError, match=r"mha:heads=5.*5 query heads "
                                         r"over 2 K/V heads"):
        TransformerConfig(layer_pattern=(("mha", "dense", 1),
                                         ("mha:heads=5", "dense", 1)), **base)
    with pytest.raises(ValueError, match=r"mha:rope=far.*\['near'\]"):
        TransformerConfig(layer_pattern=(("mha:rope=far", "dense", 1),),
                          rope_tables={"near": RopeTable()}, **base)
    for bad in ("mha:writes=kv", "mha:heads=", "mha:heads=4,heads=4",
                "mla:heads=4"):
        with pytest.raises(ValueError, match="does not take"):
            TransformerConfig(layer_pattern=((bad, "dense", 1),), **base)
    with pytest.raises(ValueError, match="attn_out_gate"):
        TransformerConfig(attn_out_gate="elementwise", **base)
    # a window run under another objective's mask says so
    from ray_tpu.models.transformer import apply_layer
    from ray_tpu.ops.attention_mask import FULL
    cfg = TransformerConfig(layer_pattern=(("mha:window=4", "dense", 1),),
                            dtype=jnp.float32, **base)
    lp = jax.tree.map(lambda a: a[0], init_params(
        jax.random.PRNGKey(0), cfg)["layers"])
    x = jnp.zeros((1, 16, 32))
    with pytest.raises(ValueError, match="brings its own mask"):
        apply_layer(x, lp, jnp.arange(16)[None], cfg, mask=FULL)
    # a mesh takes a run's heads or refuses the run by its name
    from ray_tpu.models.transformer import _check_mesh

    class Mesh:
        def __init__(self, **shape):
            self.shape = shape

    mixed = TransformerConfig(
        layer_pattern=(("mha:heads=2", "dense", 1),
                       ("mha:heads=6,window=4", "dense", 1)), **base)
    _check_mesh(mixed, Mesh(dp=2, tp=2))
    with pytest.raises(ValueError, match=r"2 K/V heads on tp=4"):
        _check_mesh(mixed, Mesh(tp=4))
    with pytest.raises(ValueError, match=r"mha:heads=6,window=4.*sp=2"):
        _check_mesh(mixed, Mesh(sp=2))
    # multi-head and causal: the ring takes it
    _check_mesh(TransformerConfig(**dict(base, n_kv_heads=4), n_layers=1),
                Mesh(sp=2, tp=2))


def test_the_window_run_is_the_full_run_under_a_window_that_holds_the_row():
    """A window as long as the row is the causal mask: the window run's
    layer equals the same weights run causally."""
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                head_dim=8, d_ff=48, max_seq_len=16, dtype=jnp.float32,
                attn_out_gate="head")
    wide = TransformerConfig(layer_pattern=(("mha:window=16", "dense", 2),),
                             **base)
    narrow = TransformerConfig(layer_pattern=(("mha:window=3", "dense", 2),),
                               **base)
    causal = TransformerConfig(n_layers=2, **base)
    params = init_params(jax.random.PRNGKey(1), causal)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 17),
                                          0, 64)}
    want, counted = loss_and_counters(params, batch, causal)
    got, counted_wide = loss_and_counters(params, batch, wide)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert "attn_gate_mean" in counted and \
        "attn_window_gate_mean" in counted_wide
    assert abs(float(loss_and_counters(params, batch, narrow)[0])
               - float(want)) > 1e-5


@pytest.mark.parametrize("window", [None, 5, 16, 40, 64])
def test_the_references_attention_by_blocks_is_the_whole_rows(window,
                                                               monkeypatch):
    """The reference attends a block of queries at a time over the keys
    the block can see at all: at four blocks of 16 it equals the masked
    softmax over the whole row, for windows below, at and above a block
    and as long as the row."""
    from benchmarks.reference import swa_gqa_moe as reference
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (64, 3, 8))
               for i in range(3))
    i, j = jnp.arange(64)[:, None], jnp.arange(64)[None, :]
    allowed = j <= i if window is None else (j <= i) & (i - j < window)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * 0.3
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
        jnp.where(allowed, scores, -jnp.inf), axis=-1), v)
    monkeypatch.setattr(reference, "_QUERY_BLOCK", 16)
    got = reference._attend(q, k, v, 0.3, window, "float32")
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-6
