"""The one expert layer (``models/moe.py``): top-k routing without drops
over the experts held, against a plain loop over experts; the share
test of an expert-parallel deployment; the chunked dispatch under a
router so skewed that one chunk is not enough."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.moe import (alike_choices, balance_loss, chunk_rows,
                                counters, init_moe_params, moe_ffn)

D, F, E, K = 32, 48, 32, 8


def _layer(skew=None, scale=20.0):
    """One layer's parameters (large enough that routing is decided).
    ``skew`` (expert -> logit): feature 0 of every token is 1 (see
    ``_tokens``) and carries that much of the expert's logit."""
    lp = jax.tree.map(lambda a: a[0] * scale, init_moe_params(
        jax.random.PRNGKey(0), 1, D, F, E, E, jnp.float32))
    for e, shift in (skew or {}).items():
        lp["wr"] = lp["wr"].at[:, e].set(0.0).at[0, e].set(shift)
    return lp


def _tokens(rows=2, length=64, key=1):
    x = jax.random.normal(jax.random.PRNGKey(key), (rows, length, D))
    return x.at[..., 0].set(1.0)


def _loop(x, lp, first=0, count=E, top_k=K, norm=True):
    """The layer as a loop over the held experts ``[first, first +
    count)`` of the whole layer's ``lp``, every token through every one
    of them with its gate (nought where it was not chosen)."""
    xt = x.reshape(-1, D)
    probs = jax.nn.softmax(xt @ lp["wr"], -1)
    gate, chosen = jax.lax.top_k(probs, top_k)
    if norm:
        gate = gate / gate.sum(-1, keepdims=True)
    y = jnp.zeros_like(xt)
    for j in range(count):
        g = jnp.sum(jnp.where(chosen == first + j, gate, 0.0), -1)
        e = first + j
        y = y + g[:, None] * ((jax.nn.silu(xt @ lp["w1"][e])
                               * (xt @ lp["w3"][e])) @ lp["w2"][e])
    return y.reshape(x.shape), chosen


def _held(lp, first, count):
    return dict(wr=lp["wr"], **{k: lp[k][first:first + count]
                                for k in ("w1", "w3", "w2")})


@pytest.mark.parametrize("norm", [True, False])
def test_top8_of_32_under_a_skewed_router_drops_nothing(norm):
    """(c) Expert 3 is nearly every token's first choice and expert 5
    nobody's: the result and every gradient still equal the loop's."""
    lp = _layer(skew={3: 30.0, 5: -30.0})
    x = _tokens()
    want, chosen = _loop(x, lp, norm=norm)
    load = np.bincount(np.asarray(chosen).ravel(), minlength=E)
    assert load[3] == x.shape[0] * x.shape[1] and load[5] == 0
    got, stats = jax.jit(lambda x, lp: moe_ffn(x, lp, K, norm))(x, lp)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-4 * float(
        jnp.max(jnp.abs(want)))
    assert int(stats["dropped_choices"]) == 0
    assert int(stats["held_choices"]) == load.sum() == x.size // D * K
    assert np.array_equal(np.asarray(stats["expert_load"]), load)
    c = counters(stats)
    assert float(c["moe_expert_load_max"]) == pytest.approx(1 / K)
    assert float(c["moe_dropped_choices"]) == 0.0

    def loss(fn):
        return lambda lp, x: jnp.sum(fn(x, lp) ** 2)

    got_g = jax.grad(loss(lambda x, lp: moe_ffn(x, lp, K, norm)[0]),
                     (0, 1))(lp, x)
    want_g = jax.grad(loss(lambda x, lp: _loop(x, lp, norm=norm)[0]),
                      (0, 1))(lp, x)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * float(
            jnp.max(jnp.abs(w)))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """(d) model-configs section 4: eight ranks hold four experts each;
    the partial results of all the shares add up to the whole layer's,
    and each share equals the loop over its own experts."""
    lp = _layer()
    x = _tokens()
    whole, _ = moe_ffn(x, lp, K, True)
    total, held = 0.0, 0
    for rank in range(8):
        part, stats = moe_ffn(x, _held(lp, 4 * rank, 4), K, True,
                              held=(4 * rank, 4))
        want, _ = _loop(x, lp, first=4 * rank, count=4)
        assert float(jnp.max(jnp.abs(part - want))) <= 1e-4 * float(
            jnp.max(jnp.abs(whole)))
        assert int(stats["dropped_choices"]) == 0
        total, held = total + part, held + int(stats["held_choices"])
    assert held == x.size // D * K
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-4 * float(
        jnp.max(jnp.abs(whole)))
    with pytest.raises(ValueError, match="experts' weights"):
        moe_ffn(x, _held(lp, 0, 4), K, True, held=(0, 8))


@pytest.mark.parametrize("rows, length, count, chunks", [
    (4, 128, 4, (1536, 512, 1)),    # the first chunk and one whole one more
    (3, 100, 6, (1280, 512, 2)),    # ... and two more, the last nearly empty
])
def test_a_share_that_gets_most_of_the_choices_takes_several_chunks(
        rows, length, count, chunks):
    """A rank holding 4 (6) of 32 experts sizes its first chunk for
    tokens that route alike: three (four) choices of every token (8
    experts of 32 include more of the held ones in under one case in a
    hundred), and what is left over goes in chunks of one choice of
    every token; a router that sends every token to all the held experts
    fills the first chunk and more, and nothing is dropped."""
    lp = _layer(skew={8 + j: 30.0 - j for j in range(count)})
    x = _tokens(rows, length, key=2)
    tokens = rows * length
    first, rest, more = chunks
    assert chunk_rows(tokens, E, count, K) == (first, rest)
    part, stats = jax.jit(lambda x, lp: moe_ffn(
        x, lp, K, True, held=(8, count)))(x, _held(lp, 8, count))
    assert int(stats["held_choices"]) == count * tokens
    assert first + (more - 1) * rest < count * tokens <= first + more * rest
    assert int(stats["dropped_choices"]) == 0
    want, _ = _loop(x, lp, first=8, count=count)
    assert float(jnp.max(jnp.abs(part - want))) <= 1e-4 * float(
        jnp.max(jnp.abs(want)))
    got_g = jax.grad(lambda lp: jnp.sum(moe_ffn(
        x, lp, K, True, held=(8, count))[0] ** 2))(_held(lp, 8, count))
    want_g = _held(jax.grad(lambda lp: jnp.sum(_loop(
        x, lp, first=8, count=count)[0] ** 2))(lp), 8, count)
    for name in ("wr", "w1", "w3", "w2"):
        assert float(jnp.max(jnp.abs(got_g[name] - want_g[name]))) <= \
            1e-4 * float(jnp.max(jnp.abs(want_g[name]))), name


def test_the_chunks_come_from_the_layers_own_shape():
    assert alike_choices(E, 4, K) == 3
    # the cell's: 32,768 positions, 8 experts of 128 each, 16 held
    assert alike_choices(128, 16, 8) == 3
    assert chunk_rows(32768, 128, 16, 8) == (3 * 32768, 32768)
    # everything held: the first chunk takes every choice
    assert chunk_rows(512, E, E, K)[0] == 512 * K
    assert alike_choices(4, 1, 2) == 1


def test_the_balance_loss_is_one_at_balance_and_pushes_towards_it():
    """Top-k load balance: 1 under a uniform router, above it where the
    choices and the probabilities pile on the same experts; its
    gradient lowers the crowded experts' logits."""
    x = _tokens()
    uniform = dict(_layer(), wr=jnp.zeros((D, E)))
    _, stats = moe_ffn(x, uniform, K)
    assert float(balance_loss(stats)) == pytest.approx(1.0, rel=1e-6)
    assert int(stats["router_load"].sum()) == x.size // D * K
    assert int(stats["tokens"]) == x.size // D
    assert stats["choices"].shape == x.shape[:-1] + (K,)

    skewed = _layer(skew={3: 30.0, 5: -30.0})
    _, stats = moe_ffn(x, skewed, K)
    assert float(balance_loss(stats)) > 1.5
    assert float(counters(stats)["moe_balance_loss"]) == pytest.approx(
        float(balance_loss(stats)))
    # a milder skew, where the softmax still has a slope; feature 0 is
    # 1 on every token, so its row is the experts' bias
    mild = _layer(skew={3: 3.0, 5: -3.0}, scale=1.0)
    g = jax.grad(lambda wr: balance_loss(moe_ffn(
        x, dict(mild, wr=wr), K)[1]))(mild["wr"])
    assert float(g[0, 3]) > 0 > float(g[0, 5])
