"""The one expert layer (``models/moe.py``): top-k routing without drops
over the experts held, against a plain loop over experts; the share
test of an expert-parallel deployment; the chunked dispatch under a
router so skewed that one chunk is not enough."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
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


def _loop(x, lp, first=0, count=E, top_k=K, norm=True, scoring="softmax",
          route_scale=1.0):
    """The layer as a loop over the held experts ``[first, first +
    count)`` of the whole layer's ``lp``, every token through every one
    of them with its gate (nought where it was not chosen)."""
    xt = x.reshape(-1, D)
    if scoring == "softmax":
        probs = jax.nn.softmax(xt @ lp["wr"], -1)
        gate, chosen = jax.lax.top_k(probs, top_k)
    else:
        probs = jax.nn.sigmoid(xt @ lp["wr"])
        _, chosen = jax.lax.top_k(probs + lp["bias"], top_k)
        gate = jnp.take_along_axis(probs, chosen, axis=-1)
    if norm:
        gate = gate / gate.sum(-1, keepdims=True)
    gate = gate * route_scale
    y = _experts_loop(xt, gate, chosen, _held(lp, first, count), first)
    return y.reshape(x.shape), chosen


def _experts_loop(xt, gate, chosen, w, first):
    """What the held experts ``w`` (``w1``/``w3``/``w2`` of the experts
    from ``first`` on) give for tokens ``xt`` [T, D] whose choices
    ``chosen`` [T, k] have the gates ``gate`` [T, k]: a function of the
    gates, so ``jax.grad`` of it has the gates' gradient too."""
    y = jnp.zeros_like(xt)
    for j in range(w["w1"].shape[0]):
        g = jnp.sum(jnp.where(chosen == first + j, gate, 0.0), -1)
        y = y + g[:, None] * ((jax.nn.silu(xt @ w["w1"][j])
                               * (xt @ w["w3"][j])) @ w["w2"][j])
    return y


def _held(lp, first, count):
    """The share ``[first, first + count)`` of the layer's ``lp``: the
    whole router (and its bias, where it has one), those experts."""
    return dict({k: lp[k] for k in ("wr", "bias") if k in lp},
                **{k: lp[k][first:first + count] for k in ("w1", "w3", "w2")})


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


# -- PR 34: how a chunk's rows return to their tokens, and what the
# backward reads on the way --------------------------------------------

def _inner_jaxprs(eqn):
    """The jaxprs inside an equation (loops, conditionals, hand-written
    derivatives, ``jit``)."""
    for value in eqn.params.values():
        for inner in (value if isinstance(value, (list, tuple))
                      else [value]):
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                yield inner


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _inner_jaxprs(eqn):
            yield from _equations(inner)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("direction", ["forward", "gradient"])
def test_what_the_layer_gathers_and_scatters(direction, scoring):
    """On bfloat16 tokens, forward and backward: (a) the rows are added
    to their tokens by ONE float32 scatter-add a chunk and direction,
    and nothing else with ``D`` columns is scattered; (b) every gather
    of rows says its indices are in range (no pass that fills rows with
    NaN follows it) and reads the tokens' dtype -- the cotangent too,
    never float32 rows; (c) no gate is gathered a row at a time (the
    sort carries them); (d) the router's choice scatters nothing into
    [T, E] and gathers nothing out of it."""
    bf = jnp.bfloat16
    lp = {k: v.astype(bf) for k, v in _layer().items()}
    kwargs = {"scoring": scoring}
    if scoring == "sigmoid":
        lp["bias"] = jnp.linspace(-0.2, 0.2, E)
    x = _tokens().astype(bf)
    tokens = x.shape[0] * x.shape[1]

    def layer(x, lp):
        return moe_ffn(x, lp, K, True, held=(8, 4), **kwargs)[0]

    fn = layer if direction == "forward" else jax.grad(
        lambda x, lp: jnp.sum(layer(x, lp).astype(jnp.float32) ** 2), (0, 1))
    jaxpr = jax.make_jaxpr(fn)(x, _held(lp, 8, 4)).jaxpr
    eqns = list(_equations(jaxpr))
    wide_adds = [e for e in eqns if e.primitive.name == "scatter-add"
                 and e.invars[0].aval.shape[-1:] == (D,)]
    # the first chunk and the loop's body, in each direction traced
    assert len(wide_adds) == (2 if direction == "forward" else 4)
    assert all(e.invars[0].aval.dtype == jnp.float32
               and e.invars[0].aval.shape == (tokens, D) for e in wide_adds)
    assert not [e for e in eqns if e.primitive.name == "scatter"
                and e.invars[0].aval.shape[-1:] == (D,)]
    row_gathers = [e for e in eqns if e.primitive.name == "gather"
                   and e.invars[0].aval.shape == (tokens, D)]
    # the tokens' rows, and in the backward their cotangent's as well
    assert len(row_gathers) == (2 if direction == "forward" else 6)
    for e in row_gathers:
        assert e.params["mode"] in (jax.lax.GatherScatterMode.CLIP,
                                    jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS)
        assert e.invars[0].aval.dtype == bf
    others = [e for e in eqns
              if e.primitive.name in ("gather", "scatter", "scatter-add")
              and e not in wide_adds and e not in row_gathers]
    for e in others:
        shape = e.invars[0].aval.shape
        assert shape != (tokens * K,) or e.primitive.name == "scatter-add", e
        assert shape != (tokens, E), e
    # (e) PR 43: a chunk makes three grouped products forward and eight
    # backward -- ``h @ w2`` is not made again for the gates' gradient,
    # which comes with the hidden rows' cotangent from one product of the
    # tokens' cotangent with ``w2`` transposed -- in two bodies, the first
    # chunk's and the loop's; and the gathered rows, the cotangent's too,
    # are read by grouped products alone: nothing widens, scales or masks
    # them at [rows, D]
    products = [e for e in eqns if e.primitive.name == "ragged_dot_general"]
    assert len(products) == (2 * 3 if direction == "forward"
                             else 2 * (3 + 8))
    readers = list(_readers_of_gathered_rows(jaxpr, (tokens, D)))
    # the tokens' rows into ``w1`` and ``w3``, forward; backward those and
    # their two transposes, the cotangent's into ``w2``'s two
    assert len(readers) == (2 * 2 if direction == "forward"
                            else 2 * 2 + 2 * (4 + 2))
    assert {e.primitive.name for e in readers} == {"ragged_dot_general"}
    rows = set(chunk_rows(tokens, E, 4, K))
    wide = [e for e in eqns if e.primitive.name == "convert_element_type"
            and e.outvars[0].aval.dtype == jnp.float32
            and e.outvars[0].aval.shape in {(r, D) for r in rows}]
    # a chunk's rows are widened where they are added to their tokens,
    # once a body and direction (the parent's backward: three times)
    assert len(wide) == (2 if direction == "forward" else 4)


def _readers_of_gathered_rows(jaxpr, shape):
    """The equations, at any depth, that read what a gather of rows out
    of an array of ``shape`` gave (the gather itself, or the ``jnp.take``
    around it)."""
    def gathers(eqn):
        return (eqn.primitive.name == "gather"
                and eqn.invars[0].aval.shape == shape)

    gathered = set()
    for eqn in jaxpr.eqns:
        if any(id(v) in gathered for v in eqn.invars):
            yield eqn
        inner = list(_inner_jaxprs(eqn))
        if gathers(eqn) or (eqn.primitive.name == "jit" and any(
                gathers(e) for j in inner for e in j.eqns)):
            gathered.update(id(v) for v in eqn.outvars)
        else:
            for j in inner:
                yield from _readers_of_gathered_rows(j, shape)


@pytest.mark.parametrize("traced", [False, True])
def test_the_sort_carries_every_choices_gate(traced):
    """``_sorted_choices``: held choices first, by their local expert,
    in token order within one (stable); the gates in the same order
    (``gates[r] == gate[order[r]]``), padded to whole chunks -- with a
    held range whose first index is traced as well."""
    x = _tokens()
    tokens = x.shape[0] * x.shape[1]
    expert = jax.random.randint(jax.random.PRNGKey(5), (tokens * K,), 0, E)
    gate = jax.random.uniform(jax.random.PRNGKey(6), (tokens * K,))
    chunks = chunk_rows(tokens, E, 4, K)

    def sort(first):
        local = expert - first
        key = jnp.where((local >= 0) & (local < 4), local, 4)
        return key, moe._sorted_choices(chunks, key.astype(jnp.int32), gate)

    key, (order, gates) = (jax.jit(sort) if traced else sort)(
        jnp.asarray(8) if traced else 8)
    n = tokens * K
    whole = chunks[0] + -(-(n - chunks[0]) // chunks[1]) * chunks[1]
    assert order.shape == gates.shape == (whole,)
    order, gates, key = (np.asarray(a) for a in (order, gates, key))
    assert sorted(order[:n]) == list(range(n))
    assert np.array_equal(gates[:n], np.asarray(gate)[order[:n]])
    sorted_key = key[order[:n]]
    assert np.all(np.diff(sorted_key) >= 0)
    # stable: within one key the choices keep their order
    assert np.all((np.diff(order[:n]) > 0) | (np.diff(sorted_key) > 0))
    held = int((key < 4).sum())
    assert np.all(sorted_key[:held] < 4) and np.all(sorted_key[held:] == 4)
    assert not order[n:].any() and not gates[n:].any()


def _case(name):
    """-> (x, whole layer's lp, held range, keywords of ``moe_ffn`` and
    of ``_loop``, relative tolerance)."""
    x, held, kwargs, tol = _tokens(), (8, 4), {}, 1e-4
    lp = _layer()
    if name == "all_of_a_tokens_choices_in_one_chunk":
        held = (0, E)                       # everything held: one chunk
    elif name == "a_tokens_choices_split_across_two_chunks":
        # every token takes all four held experts: three of its choices
        # lie in the first chunk, the fourth in a further one
        lp = _layer(skew={8 + j: 30.0 - j for j in range(4)})
        x = _tokens(4, 128, key=2)
    elif name == "tokens_with_no_held_choice":
        lp = _layer(skew={8: -30.0, 9: -30.0, 10: -30.0})
    elif name == "sigmoid_router_with_a_bias":
        kwargs = {"scoring": "sigmoid", "route_scale": 2.5}
        lp = dict(_layer(scale=4.0),
                  bias=jnp.linspace(-0.3, 0.3, E)[::-1])
    elif name == "bfloat16_inputs":
        lp = _layer(skew={8: 30.0, 9: 29.0})
        tol = 2e-2
    return x, lp, held, kwargs, tol


@pytest.mark.parametrize("name", [
    "all_of_a_tokens_choices_in_one_chunk",
    "a_tokens_choices_split_across_two_chunks",
    "tokens_with_no_held_choice",
    "sigmoid_router_with_a_bias",
    "bfloat16_inputs"])
def test_the_rows_return_to_their_tokens_as_in_the_plain_loop(name):
    """Value and every gradient against ``_loop`` where adding a chunk's
    rows to their tokens can go wrong (the file's 1e-4; bfloat16 inputs
    against the float32 loop over the same rounded numbers, within
    bfloat16's rounding: the sums are float32)."""
    x, lp, (first, count), kwargs, tol = _case(name)
    held_lp = _held(lp, first, count)
    dtype = jnp.bfloat16 if name == "bfloat16_inputs" else jnp.float32
    if dtype == jnp.bfloat16:
        x, lp, held_lp = jax.tree.map(
            lambda a: a.astype(dtype).astype(jnp.float32), (x, lp, held_lp))

    def layer(x, lp):
        x, lp = jax.tree.map(lambda a: a.astype(dtype), (x, lp))
        if "bias" in lp:
            lp["bias"] = lp["bias"].astype(jnp.float32)
        y, stats = moe_ffn(x, lp, K, True, held=(first, count), **kwargs)
        return y.astype(jnp.float32), stats

    def loop(x, lp):
        return _loop(x, lp, first=first, count=count, **kwargs)

    got, stats = jax.jit(layer)(x, held_lp)
    want, chosen = loop(x, lp)
    chosen = np.asarray(chosen)
    in_range = (chosen >= first) & (chosen < first + count)
    assert int(stats["held_choices"]) == in_range.sum()
    assert int(stats["dropped_choices"]) == 0
    tokens = chosen.shape[0]
    first_chunk = chunk_rows(tokens, E, count, K)[0]
    if name == "all_of_a_tokens_choices_in_one_chunk":
        assert in_range.all() and first_chunk == tokens * K
    elif name == "a_tokens_choices_split_across_two_chunks":
        assert (in_range.sum(-1) == 4).all() and first_chunk == 3 * tokens
    elif name == "tokens_with_no_held_choice":
        assert (in_range.sum(-1) == 0).sum() > tokens // 2 \
            and in_range.any()
    assert float(jnp.max(jnp.abs(got - want))) <= tol * float(
        jnp.max(jnp.abs(want)))
    got_g = jax.grad(lambda x, lp: jnp.sum(layer(x, lp)[0] ** 2),
                     (0, 1))(x, held_lp)
    want_x, want_lp = jax.grad(lambda x, lp: jnp.sum(loop(x, lp)[0] ** 2),
                               (0, 1))(x, lp)
    want_g = (want_x, _held(want_lp, first, count))
    assert jax.tree.structure(got_g) == jax.tree.structure(want_g)
    for path, g in jax.tree_util.tree_flatten_with_path(got_g)[0]:
        w = want_g
        for k in path:
            w = w[getattr(k, "key", getattr(k, "idx", None))]
        assert float(jnp.max(jnp.abs(g - w))) <= tol * max(
            float(jnp.max(jnp.abs(w))), 1e-30), (path, name)


# -- PR 43: the backward takes the gates' gradient and the hidden rows'
# cotangent from one product ---------------------------------------------

def _routed(load, scoring):
    """-> (tokens [T, D], the whole layer's ``lp``, held range, gates
    [T, k] and choices [T, k] as the router of ``scoring`` gives them)."""
    if load == "padded_rows_in_the_last_group":
        # under the first chunk's rows: the rows past the last held
        # choice ride in the last group
        x, lp, held = _tokens(), _layer(), (8, 4)
    elif load == "a_second_chunk_filled_to_its_last_row":
        # every token takes all four held experts: 3 x 512 rows, then 512
        x, held = _tokens(4, 128, key=2), (8, 4)
        lp = _layer(skew={8 + j: 30.0 - j for j in range(4)})
    elif load == "a_second_and_a_third_chunk_the_last_nearly_empty":
        x, held = _tokens(3, 100, key=2), (8, 6)
        lp = _layer(skew={8 + j: 30.0 - j for j in range(6)})
    xt = x.reshape(-1, D)
    if scoring == "softmax":
        gate, chosen = jax.lax.top_k(jax.nn.softmax(xt @ lp["wr"], -1), K)
        gate = gate / gate.sum(-1, keepdims=True)
    else:
        # not renormalised, times a route scale: gates that sum to ~5
        gate, chosen = jax.lax.top_k(jax.nn.sigmoid(xt @ lp["wr"] / 5.0), K)
        gate = 2.5 * gate
    return xt, lp, held, gate, chosen


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("load", [
    "padded_rows_in_the_last_group",
    "a_second_chunk_filled_to_its_last_row",
    "a_second_and_a_third_chunk_the_last_nearly_empty"])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_the_held_experts_five_gradients_are_the_plain_loops(
        scoring, load, dtype):
    """``_held_experts`` itself, under either router's gates: the
    gradients in the gates, the tokens and the three weights equal
    ``jax.grad`` of the plain loop (float32 to 1e-5; bfloat16 inputs
    against the float32 loop over the same rounded numbers within
    bfloat16's rounding, as above: the sums are float32)."""
    xt, lp, (first, count), gate, chosen = _routed(load, scoring)
    tokens = xt.shape[0]
    dtype = jnp.dtype(dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    w = {k: lp[k][first:first + count] for k in ("w1", "w3", "w2")}
    xt, w = jax.tree.map(lambda a: a.astype(dtype).astype(jnp.float32),
                         (xt, w))
    chunks = chunk_rows(tokens, E, count, K)
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local,
                    count).astype(jnp.int32)
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    held = int(sizes.sum())
    if load == "padded_rows_in_the_last_group":
        assert 0 < held < chunks[0]
    elif load == "a_second_chunk_filled_to_its_last_row":
        assert held == chunks[0] + chunks[1]
    else:
        assert chunks[0] + chunks[1] < held < chunks[0] + 2 * chunks[1]

    def held_experts(xt, gate, w):
        xt, w = jax.tree.map(lambda a: a.astype(dtype), (xt, w))
        y, done = moe._held_experts(
            chunks, K, xt, gate.reshape(-1), (w["w1"], w["w3"], w["w2"]),
            key, jnp.cumsum(sizes), sizes)
        return y.astype(jnp.float32), done

    def loop(xt, gate, w):
        return _experts_loop(xt, gate, chosen, w, first)

    got, done = jax.jit(held_experts)(xt, gate, w)
    want = loop(xt, gate, w)
    assert int(done) == held
    assert float(jnp.max(jnp.abs(got - want))) <= tol * float(
        jnp.max(jnp.abs(want)))
    got_g = jax.jit(jax.grad(lambda *a: jnp.sum(held_experts(*a)[0] ** 2),
                             (0, 1, 2)))(xt, gate, w)
    want_g = jax.grad(lambda *a: jnp.sum(loop(*a) ** 2), (0, 1, 2))(
        xt, gate, w)
    for name, g, w_g in zip(
            ("xt", "gate", "w1", "w2", "w3"), jax.tree.leaves(got_g),
            jax.tree.leaves(want_g)):
        assert g.shape == w_g.shape
        assert float(jnp.max(jnp.abs(g - w_g))) <= tol * float(
            jnp.max(jnp.abs(w_g))), name
    # a choice of an expert that is not held has no gradient here
    absent = np.asarray(key).reshape(tokens, K) == count
    assert not np.asarray(got_g[1])[absent].any()
