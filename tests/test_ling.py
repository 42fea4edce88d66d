"""Layers as Ling-3.0 lays them out: Kimi Delta Attention
(``models/kda.py`` over ``ops/kda.py``), latent attention without a
query latent and with a norm of each query and key head
(``models/mla.py``), a dense layer, and expert layers whose sigmoid
router chooses within the best groups of experts (``models/moe.py``).
At a tiny size against the benchmark's plain reference
(``benchmarks/reference/kda_mla_moe.py``), which runs KDA's rule token by
token."""

import functools
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
from ray_tpu.models.transformer import (  # noqa: E402
    make_train_state, make_train_step)

# The published layers 1, 10 and 11 (a KDA layer with the dense FFN,
# then the last KDA layer of a period and its MLA layer, each with the
# expert layer) at a tiny width: 2 heads of 16 (KDA
# in chunks of 16 over rows of 32: two chunks a row), MLA's 8 + 8 score
# and 16 value columns over a latent of 16, 16 experts of 16 in 4 groups
# of which a token keeps 2, 4 a token times 2.5, this rank holding 4
# (experts 4-7, group 1), a shared expert of 16 and a dense layer of 32.
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "ling-3.0-flash-train.json")) as f:
    CONFIG = dict(
        json.load(f), hidden_size=48, num_attention_heads=2, head_dim=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=16, chunk_size=16, num_experts=16, n_group=4,
        topk_group=2, num_experts_held=4, experts_held_first=4,
        num_experts_per_tok=4, moe_intermediate_size=16,
        moe_shared_expert_intermediate_size=16, intermediate_size=32,
        vocab_size=128, dtype="float32", num_hidden_layers=3,
        kept_layers=[1, 10, 11])
TRAFFIC = {"kind": "packed_documents", "rows": 2, "seq_len": 32,
           "pool_batches": 2, "doc_len": {"alpha": 1.2, "min": 4, "max": 64},
           "bos_id": 0}
CELL = {"check": {"steps": 2}}
# float32 on both sides, the reference following the program's experts:
# summation order and the chunked rule against the recurrence.
LIMITS = {"grad1_norm_gap": 1e-4, "change_norm_gap": 1e-3,
          "routing_gap": 1e-5}
LOSS_GAP = 1e-5


def _driver():
    from benchmarks.drivers import trainer_kda_mla_steps as driver
    return driver


def _cfg(**changes):
    driver = _driver()
    kwargs = driver.model_kwargs(CONFIG, TRAFFIC["seq_len"])
    return driver.transformer_config(dict(kwargs, **changes), jnp.float32)


def _batches(seed):
    from benchmarks.harness import traffic
    return list(traffic.generate(TRAFFIC, seed, vocab_size=128))


def _program(seed, batches):
    """Two steps of ``make_train_step`` from the seed's weights -> what
    the reference returns."""
    from benchmarks.drivers.trainer_steps import _adam_mu
    from benchmarks.drivers.trainer_swa_moe_steps import leaf_norms
    from benchmarks.harness import kda_weights
    cfg = _cfg()
    state, tx = make_train_state(
        jax.random.PRNGKey(0), cfg,
        learning_rate=CONFIG["optimizer"]["learning_rate"])
    start = kda_weights.make_decoder(seed, CONFIG, jnp.float32)
    assert jax.tree.map(jnp.shape, start) == jax.tree.map(
        jnp.shape, state["params"])
    state["params"] = start
    step = make_train_step(cfg, tx)
    out = {"losses": [], "metrics": [], "choices": []}
    for i, batch in enumerate(batches):
        state, metrics = step(state, {"tokens": jnp.asarray(batch)})
        out["losses"].append(float(metrics["loss"]))
        out["choices"].append(np.asarray(metrics.pop("moe_choices")))
        out["metrics"].append({k: float(v) for k, v in metrics.items()})
        if i == 0:
            out["grad1_norm"] = {
                k: np.asarray(v, np.float64) / (1.0 - 0.9) for k, v in
                leaf_norms(_adam_mu(state["opt"])).items()}
    again = kda_weights.make_decoder(seed, CONFIG, jnp.float32)
    out["change_norm"] = {k: np.asarray(v, np.float64) for k, v in
                          leaf_norms(jax.tree.map(
                              lambda a, b: a - b, state["params"],
                              again)).items()}
    out["moe_bias"] = np.asarray(state["moe_bias"])
    return out


def _numbers(prog, ref):
    from benchmarks.harness import compare
    return dict(compare.train_numbers(prog, ref),
                routing_gap=ref["routing_gap"])


@pytest.fixture(scope="module")
def stepped():
    """The program's two steps and the reference's on them, once."""
    driver = _driver()
    seed = 2**31 + 51
    batches = _batches(seed)
    prog = _program(seed, batches)
    ref = driver.follow_reference(CELL, CONFIG, seed, batches,
                                  choices=prog["choices"])
    return seed, batches, prog, ref


def test_the_tiny_model_is_the_plain_reference(stepped):
    """The whole loss, the first gradient leaf by leaf, the parameters'
    change over two AdamW steps and the routers' bias after them."""
    from benchmarks.harness import compare
    _, _, prog, ref = stepped
    # 2 expert layers, 2 rows x 32 positions, 4 choices
    assert prog["choices"][0].shape == (2, 2, 32, 4)
    assert max(compare.loss_gaps(prog, ref)) <= LOSS_GAP
    correct, compared = compare.judge(_numbers(prog, ref), LIMITS)
    assert correct, compared
    np.testing.assert_array_equal(prog["moe_bias"], ref["moe_bias"])
    leaves = set(prog["grad1_norm"])
    assert {"layers.0.kda.w_qkv", "layers.0.kda.A_log",
            "layers.0.kda.dt_bias", "layers.0.kda.w_gate", "layers.0.w1",
            "layers.1.kda.conv", "layers.1.moe.wr", "layers.2.mla.wq",
            "layers.2.mla.q_head_norm", "layers.2.mla.k_head_norm",
            "embed", "lm_head"} <= leaves
    for metrics in prog["metrics"]:
        assert metrics["moe_dropped_choices"] == 0.0
        assert metrics["kda_fallback_passes"] == 1.0      # off the TPU
        assert 0.0 < metrics["kda_decay_mean"] < 1.0


@pytest.mark.parametrize("how", [dict(decay="head"), dict(groups=False),
                                 dict(gate="softplus")])
def test_each_control_fails_the_comparison(stepped, how):
    """One decay a head, the group step left out, the unbounded gate:
    each in the reference's place reads false (the state kept in
    bfloat16 is the rule's to show: ``tests/test_kda.py``)."""
    from benchmarks.harness import compare
    seed, batches, prog, _ = stepped
    control = _driver().follow_reference(CELL, CONFIG, seed, batches,
                                         choices=prog["choices"], **how)
    correct, compared = compare.judge(_numbers(prog, control), LIMITS)
    assert not correct, (how, compared)


def test_every_choice_lies_in_the_tokens_kept_groups(stepped):
    """The program's choices, by the reference's own routing: within
    the 2 best of 4 groups a token, every token, every layer."""
    _, _, prog, ref = stepped
    assert ref["routing_gap"][0] <= LIMITS["routing_gap"]
    size = CONFIG["num_experts"] // CONFIG["n_group"]
    groups = prog["choices"][0] // size
    assert (np.array([[[len(set(t)) for t in row] for row in layer]
                      for layer in groups]) <= CONFIG["topk_group"]).all()


def _router(n_group, topk_group, bias=True, seed=3, tokens=64, experts=32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (tokens, 16))
    lp = {"wr": 0.5 * jax.random.normal(keys[1], (16, experts)),
          "w1": jnp.zeros((experts, 16, 8)), "w3": jnp.zeros((experts, 16, 8)),
          "w2": jnp.zeros((experts, 8, 16))}
    if bias:
        lp["bias"] = 0.1 * jax.random.normal(keys[2], (experts,))
    _, stats = jax.jit(lambda x, lp: moe.moe_ffn(
        x, lp, 4, scoring="sigmoid", route_scale=2.5, n_group=n_group,
        topk_group=topk_group))(x, lp)
    return x, lp, stats["choices"]


@pytest.mark.parametrize("bias", [True, False])
def test_the_group_router_is_top4_of_8_groups_then_top8(bias):
    """Against plain numpy: each group scored by its two largest ``s +
    b``, the best 4 of 8 groups kept, the 8 largest within them."""
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = np.asarray(jax.random.normal(keys[0], (96, 16)), np.float64)
    wr = np.asarray(0.5 * jax.random.normal(keys[1], (16, 64)))
    b = np.asarray(0.1 * jax.random.normal(keys[2], (64,))) if bias \
        else np.zeros(64)
    lp = {"wr": jnp.asarray(wr, jnp.float32),
          "w1": jnp.zeros((64, 16, 8)), "w3": jnp.zeros((64, 16, 8)),
          "w2": jnp.zeros((64, 8, 16))}
    if bias:
        lp["bias"] = jnp.asarray(b, jnp.float32)
    _, stats = jax.jit(lambda x, lp: moe.moe_ffn(
        x, lp, 8, scoring="sigmoid", n_group=8, topk_group=4))(
            jnp.asarray(x, jnp.float32), lp)
    select = 1.0 / (1.0 + np.exp(-(x @ wr))) + b
    for t in range(x.shape[0]):
        groups = select[t].reshape(8, 8)
        score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-score)[:4]
        allowed = np.full(64, -np.inf)
        for g in kept:
            allowed[g * 8:(g + 1) * 8] = select[t, g * 8:(g + 1) * 8]
        want = set(np.argsort(-allowed)[:8])
        assert set(np.asarray(stats["choices"][t]).tolist()) == want


def test_one_group_is_todays_router_and_its_program():
    """At ``n_group`` 1 the choices and gates are the router's without
    the group step, and the traced program is the same text."""
    x, lp, plain = _router(1, 1)
    keys = dict(top_k=4, scoring="sigmoid", route_scale=2.5)
    got = jax.jit(lambda x: moe.moe_ffn(x, lp, **keys, n_group=1,
                                        topk_group=1))(x)
    want = jax.jit(lambda x: moe.moe_ffn(x, lp, **keys))(x)
    np.testing.assert_array_equal(got[1]["choices"], want[1]["choices"])
    np.testing.assert_array_equal(got[0], want[0])
    assert jax.jit(lambda x: moe.moe_ffn(x, lp, **keys, n_group=1)[0]).lower(
        x).as_text() == jax.jit(lambda x: moe.moe_ffn(x, lp, **keys)[0]
                                ).lower(x).as_text()
    # the group step does move choices here
    _, _, grouped = _router(8, 2)
    assert not np.array_equal(grouped, plain)


@functools.lru_cache(maxsize=None)
def _latent_layer(seed, q_lora_rank, qk_norm):
    """One MLA layer's output, the program's and the reference's, on
    one row at the tiny width."""
    from benchmarks.reference import kda_mla_moe as reference
    from ray_tpu.models.common import LayerCall
    from ray_tpu.models.mla import MLA
    cfg = _cfg(mla=dict(q_lora_rank=q_lora_rank, kv_lora_rank=16,
                        qk_nope_head_dim=8, qk_rope_head_dim=8,
                        v_head_dim=16, rope_interleave=False,
                        qk_norm=qk_norm))
    lp = jax.tree.map(lambda a: a[0] + 0.1 * jax.random.normal(
        jax.random.PRNGKey(seed), a[0].shape) if a.ndim == 2 else a[0],
        MLA.init(jax.random.PRNGKey(seed), 1, cfg, {}))
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 32, 48))
    call = LayerCall(cfg, positions=jnp.arange(32)[None])
    out = jax.jit(lambda h, lp: MLA.apply(h, lp, call)[0])(h, lp)
    if not qk_norm or q_lora_rank is not None:
        return out, None
    flat = {"mla." + k: v for k, v in lp["mla"].items()}
    hp = {"qk_nope": 8, "kv_rank": 16, "eps": cfg.norm_eps,
          "theta": cfg.rope_theta}
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda lp, h: reference._latent_attention(
            lp, h, hp, "float32"))(flat, h[0])
    return out, ref


@pytest.mark.parametrize("q_lora_rank", [None, 12])
def test_latent_attention_without_a_query_latent(q_lora_rank):
    """The direct query and the per-head norms against the reference; a
    query latent (JoyAI's) still builds and runs beside the norm."""
    out, ref = _latent_layer(5, q_lora_rank, qk_norm=True)
    assert out.shape == (1, 32, 48)
    if q_lora_rank is None:
        np.testing.assert_allclose(out[0], ref, rtol=2e-5, atol=2e-5)


def test_the_query_and_key_norms_change_the_layer():
    out, _ = _latent_layer(5, None, qk_norm=False)
    normed, ref = _latent_layer(5, None, qk_norm=True)
    assert float(jnp.max(jnp.abs(out - normed))) > 1e-3
    np.testing.assert_allclose(normed[0], ref, rtol=2e-5, atol=2e-5)


def test_four_ranks_shares_add_up_to_the_uncut_layer():
    """The expert layer of 16 experts, cut 4 ways: the four ranks'
    partial sums, with the shared expert counted once, are the layer
    that holds all 16, by the reference's own routing."""
    from benchmarks.reference import kda_mla_moe as reference
    keys = jax.random.split(jax.random.PRNGKey(11), 9)
    d, f, e = 48, 16, 16
    lp = {"moe.wr": jax.random.normal(keys[0], (d, e)) * 0.3}
    for i, name in enumerate(("w1", "w3")):
        lp["moe." + name] = jax.random.normal(keys[1 + i], (e, d, f)) * 0.2
    lp["moe.w2"] = jax.random.normal(keys[3], (e, f, d)) * 0.2
    for i, (name, shape) in enumerate((("ws1", (d, f)), ("ws3", (d, f)),
                                       ("ws2", (f, d)))):
        lp["moe." + name] = jax.random.normal(keys[4 + i], shape) * 0.2
    h = jax.random.normal(keys[7], (32, d))
    bias = 0.05 * jax.random.normal(keys[8], (e,))
    hp = dict(top_k=4, first=0, groups=True, n_group=4, topk_group=2,
              route_scale=2.5)
    @jax.jit
    def layers(lp, h, bias):
        whole, chosen, _ = reference._experts(lp, h, bias, hp, "float32",
                                              None)
        shared = reference._swiglu(h, lp["moe.ws1"], lp["moe.ws3"],
                                   lp["moe.ws2"], "float32")
        parts = []
        for rank in range(4):
            held = slice(4 * rank, 4 * rank + 4)
            share = dict(lp, **{"moe." + n: lp["moe." + n][held]
                                for n in ("w1", "w3", "w2")})
            y, _, _ = reference._experts(share, h, bias,
                                         dict(hp, first=4 * rank), "float32",
                                         chosen)
            parts.append(y - shared)
        return whole, sum(parts) + shared

    whole, summed = layers(lp, h, bias)
    np.testing.assert_allclose(summed, whole, rtol=1e-5, atol=1e-5)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    seen = ["benchmarks/reference/kda_mla_moe.py"]
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


def test_the_pattern_is_the_kept_published_layers():
    """The cell's: layer 1 (KDA, dense), then one period, five KDA
    layers and one MLA layer, each with the expert layer -- three runs'
    stacks; the tiny one's three runs of one."""
    from benchmarks.harness import kda_weights
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3.0-flash-train.json")) as f:
        cell = json.load(f)
    assert cell["kept_layers"] == [1, 6, 7, 8, 9, 10, 11]
    assert kda_weights.pattern_of(cell) == [
        ("kda", "dense", 1), ("kda", "moe", 5), ("mla", "moe", 1)]
    cfg = _cfg()
    assert cfg.layer_pattern == (("kda", "dense", 1), ("kda", "moe", 1),
                                 ("mla", "moe", 1))
    assert cfg.moe_layers == 2
