"""Mixture-of-experts FFN: top-k routing without drops over the experts
held here.

One expert layer for every deployment shape.  The router scores all
``E`` experts in float32, each token takes its ``top_k`` largest
(renormalised over the chosen ones when ``norm_topk``), and this
program computes the part of the result that the experts it *holds* --
a contiguous range ``held = (first, count)`` -- give for the
token-choices routed to them.  What absent experts would add is left
out: on one chip of an expert-parallel deployment that partial result
is what goes on; under an ``ep`` mesh axis every shard runs this same
layer on its own range and the partial results are summed with ``psum``
(``moe_ffn_sharded``).

Per layer, with T tokens, N = T * top_k token-choices:
    probs    = softmax(x @ wr)                      [T, E]  float32
    gate, e  = top_k(probs), renormalised           [T, k]
    order    = token-choices sorted by held expert; those routed to
               absent experts last; the sort carries the gates along
    chunks   = the sorted list cut into chunks of a fixed number of
               rows; the loop ends with the last held choice (the
               backward walks the same chunks: ``_held_experts``)
    h        = silu(ragged_dot(xs, w1)) * ragged_dot(xs, w3)   SwiGLU,
               or relu(ragged_dot(xs, w1))^2        squared ReLU, no w3
    out      = ragged_dot(h, w2)        [rows, D], the products' dtype
    y[tok]  += gate * float32(out)      per chunk: a scatter-add
The experts are of one form a model (``moe_act``): SwiGLU (two matrices
in, one out) or gate-less squared ReLU (one in, one out, as Nemotron-H
has them).  Either form may work in a LATENT narrower than the model
(``moe_latent``): ``xs`` are then rows of ``x w_down`` and the share's
sum goes up through ``w_up`` -- a linear map, so the shares of an
expert-parallel deployment still add up -- while the router and the
shared expert read ``x`` at the model's width.
Nothing is dropped whatever the imbalance: the chunks cover all N
choices, so memory is bounded by the chunk and work follows the number
of chunks the held choices fill.  The rows return to their tokens by
one float32 scatter-add a chunk and direction, and that add is the only
place where a chunk's rows are widened.  The backward is written by hand
(``_held_experts_bwd``); a chunk of it, with ``dys = dy[tok]`` gathered
in the tokens' dtype and left in it:
    h        = the hidden rows again (either form)
    u        = ragged_dot(dys, w2^T)    [rows, F]: ``out`` is NOT made
    d gate   = sum_F float32(h) * float32(u)
    dh, gh   = gate * u, gate * h       one pass over [rows, F]
    d w2     = ragged_dot^T(gh, dys)
    d xs, d w1, d w3 from dh            four products (two without w3)
Eleven grouped products a layer for SwiGLU (three forward, eight
backward), seven for squared ReLU (two and five).  The
gate is a scalar a row, so it multiplies AFTER ``dys @ w2^T`` and on the
F-wide operand of ``w2``'s gradient: the one product serves the hidden
rows' cotangent and the gates' gradient (``h . u`` is ``out . dys``),
and nothing is made, scaled or masked at [rows, D] but ``d xs`` on its
way into the scatter-add (PERF.md section 6, PR 43).  The other way
round for the rows' return -- every token gathering the rows of its
``top_k`` slots through the inverse of the sort and summing them -- is
no faster on the chip: a gathered row of 2,048 bfloat16 costs 35 ns and
a scattered float32 one 83 ns, and the slots are ``top_k`` a token where
a first chunk holds ``m`` (PERF.md section 6, PR 34).

A chunk is sized for tokens that route alike (``chunk_rows``): ``m``
choices of every token, with ``m`` the most of a token's ``top_k``
experts that fall among the ``count`` held in all but one case in a
hundred.  That is the share's worst
common case, not its average: a block of identical tokens (a mask
token, a separator, a model whose features have collapsed) puts ``j``
choices of every one of them here at once, for a ``j`` drawn once for
the block, and a chunk that held the balanced load only would make a
layer's cost swing with that draw.  A layer computes one such chunk
whatever its load, and what a heavier load leaves over in chunks of one
choice of every token, as many as it fills.

``balance_loss`` is the load-balance auxiliary of top-k routing.

Two routers.  ``scoring="softmax"`` is the one above.  ``"sigmoid"`` is
the auxiliary-loss-free one (DeepSeek-V3's ``noaux_tc``):
    score    = sigmoid(x @ wr)                      [T, E]  float32
    group    = sum of the two largest score + bias in each of
               ``n_group`` equal groups of experts  [T, n_group]
    e        = top_k(score + bias) within the ``topk_group`` best
               groups                   the bias only chooses
    gate     = score[e] / (sum over e + 1e-20) * route_scale
With one group (the default) the group step is left out: ``e`` is the
``top_k`` of ``score + bias`` over all experts.
``bias`` [E] is no parameter: it is state beside the parameters, handed
to the layer as ``lp["bias"]`` (no gradient reaches it), and after each
step ``update_bias`` moves it by ``rate`` towards the experts that step
loaded least.  A layer that has one also reports every expert's load.

A shared expert (``ws1``/``ws3``/``ws2``, one SwiGLU every token
passes; ``ws1``/``ws2`` in the squared-ReLU form) is added by ``shared_expert`` OUTSIDE the share's partial sum:
on one chip's share and under an ``ep`` axis alike it counts once.  It
may have a gate of its own (``wsg`` [D, 1], ``shared_gate``): the token's
``sigmoid(x . wsg)`` multiplies its output.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ray_tpu.models.common import LayerCall, LayerKind, stacked_normal

_CHUNK_ALIGN = 256
#: The share of blocks of alike tokens a chunk may fall short of.
_ALIKE_TAIL = 0.01


def init_moe_params(rng: jax.Array, n_layers: int, d_model: int,
                    d_ff: int, n_experts: int, n_held: int, dtype,
                    shared_width: int = 0, shared_gate: bool = False,
                    act: str = "swiglu", latent: int = 0) -> Dict:
    """Router over all ``n_experts``; weights of the ``n_held`` held
    (``w3`` under ``act="swiglu"`` alone), in a latent of ``latent``
    columns between ``w_down`` and ``w_up`` where that is not 0; a
    shared expert of ``shared_width`` where that is not 0, with its
    gate ``wsg`` [d_model, 1] under ``shared_gate``."""
    keys = jax.random.split(rng, 4)
    stacked = stacked_normal(n_layers, dtype)
    d_in = latent or d_model
    params = {
        "wr": stacked(keys[0], (d_model, n_experts)),
        "w1": stacked(keys[1], (n_held, d_in, d_ff)),
        "w2": stacked(keys[3], (n_held, d_ff, d_in)),
    }
    if act == "swiglu":
        params["w3"] = stacked(keys[2], (n_held, d_in, d_ff))
    if latent:
        down, up = jax.random.split(jax.random.fold_in(rng, 6))
        params.update({"w_down": stacked(down, (d_model, latent)),
                       "w_up": stacked(up, (latent, d_model))})
    if shared_width:
        shared = jax.random.split(jax.random.fold_in(rng, 4), 3)
        params.update({
            "ws1": stacked(shared[0], (d_model, shared_width)),
            "ws2": stacked(shared[2], (shared_width, d_model)),
        })
        if act == "swiglu":
            params["ws3"] = stacked(shared[1], (d_model, shared_width))
        if shared_gate:
            params["wsg"] = stacked(jax.random.fold_in(rng, 5), (d_model, 1))
    return params


def moe_param_specs(shared: bool = False, shared_gate: bool = False,
                    act: str = "swiglu", latent: int = 0) -> Dict:
    """Experts sharded over ``ep``; router and latent pair replicated;
    the shared expert's width over ``tp``, replicated over ``ep``; its
    gate replicated."""
    gated = act == "swiglu"
    specs = {
        "wr": P(None, None),
        "w1": P(None, "ep", None, None),
        **({"w3": P(None, "ep", None, None)} if gated else {}),
        "w2": P(None, "ep", None, None),
    }
    if latent:
        specs.update({"w_down": P(None, None, None),
                      "w_up": P(None, None, None)})
    if shared:
        specs.update({"ws1": P(None, None, "tp"), "ws2": P(None, "tp", None),
                      **({"ws3": P(None, None, "tp")} if gated else {})})
        if shared_gate:
            specs["wsg"] = P(None, None, None)
    return specs


def alike_choices(n_experts: int, n_held: int, top_k: int,
                  tail: float = _ALIKE_TAIL) -> int:
    """The least ``m`` such that a token's ``top_k`` distinct experts,
    wherever they lie among the ``n_experts``, include more than ``m``
    of the ``n_held`` held ones in at most the share ``tail`` of cases
    (hypergeometric; one in a hundred by default): 3 for 8 of 128 with
    16 held; 2 for 8 of 256 with 16 held, 3 at one in a thousand."""
    total = math.comb(n_experts, top_k)
    beyond = 1.0
    for m in range(min(top_k, n_held) + 1):
        beyond -= (math.comb(n_held, m)
                   * math.comb(n_experts - n_held, top_k - m)) / total
        if beyond <= tail:
            return m
    return min(top_k, n_held)


def chunk_rows(n_tokens: int, n_experts: int, n_held: int,
               top_k: int, tail: float = _ALIKE_TAIL) -> Tuple[int, int]:
    """Rows of the first dispatch chunk, which is always computed --
    ``alike_choices`` choices of every token (at least one, and no more
    than all the choices) -- and of each further one: one choice of
    every token."""
    def aligned(rows):
        return -(-rows // _CHUNK_ALIGN) * _CHUNK_ALIGN
    first = n_tokens * max(1, alike_choices(n_experts, n_held, top_k, tail))
    return aligned(min(first, n_tokens * top_k)), aligned(n_tokens)


def _chunk_inputs(rows, top_k, n_tokens, order, ends, sizes, start):
    """Sorted rows ``[start, start + rows)``: their choices, tokens,
    which of them are held choices, and the experts' groups among them.
    The rows past the last held choice ride in the last group with a
    token of their own each (their output is masked out): every row of
    a chunk is computed, gathered and scattered alike, so a chunk's cost
    does not vary with the router's balance -- the number of chunks
    does."""
    idx = jax.lax.dynamic_slice(order, (start,), (rows,))
    row = start + jnp.arange(rows, dtype=jnp.int32)
    valid = row < ends[-1]
    tok = jnp.where(valid, idx // top_k, row % n_tokens)
    group = (jnp.clip(ends - start, 0, rows)
             - jnp.clip(ends - sizes - start, 0, rows))
    group = group.at[-1].add(rows - jnp.sum(group))
    return idx, tok, valid, group


def _chunk_hidden(xs, hidden, group):
    """[rows, D] sorted token rows -> their experts' hidden rows
    [rows, F], in the products' dtype: ``silu(xs w1) . xs w3`` for
    ``hidden = (w1, w3)``, ``relu(xs w1)^2`` for ``(w1,)``."""
    if len(hidden) == 2:
        return jax.nn.silu(jax.lax.ragged_dot(xs, hidden[0], group)) * \
            jax.lax.ragged_dot(xs, hidden[1], group)
    return jnp.square(jax.nn.relu(jax.lax.ragged_dot(xs, hidden[0], group)))


def _chunk_experts(xs, ws, group):
    """[rows, D] sorted token rows -> their experts' output, unweighted,
    in the products' dtype.  ``ws``: the hidden weights, then ``w2``."""
    with jax.named_scope("moe_experts"):
        return jax.lax.ragged_dot(_chunk_hidden(xs, ws[:-1], group), ws[-1],
                                  group)


def _token_rows(a, tok):
    """``a[tok]``, rows of [T, D] in ``a``'s dtype.  ``tok`` lies in
    range by construction (``_chunk_inputs``), and the gather says so:
    ``jnp.take``'s default would follow it with a pass over all the
    rows that fills those out of range with NaN."""
    return jnp.take(a, tok, axis=0, mode="clip")


def _over_chunks(chunks, n_held, run, carry):
    """``carry = run(rows, start, carry)`` over the first chunk, always,
    and over as many further ones as hold a held choice (a loop of as
    many turns as that takes: not differentiated, ``_held_experts``
    brings its own backward)."""
    first, rest = chunks
    carry = run(first, 0, carry)
    return jax.lax.fori_loop(
        0, jnp.maximum(0, (n_held - first + rest - 1) // rest),
        lambda c, carry: run(rest, first + c * rest, carry), carry)


def _sorted_choices(chunks, key, gate):
    """The token-choices (``token * top_k + slot``) sorted by ``key``
    [N] -- a held choice's local expert, ``count`` for the others, which
    so come last -- and their gates in that order (the sort carries them
    along: fetched a row at a time they cost a gather of scalars a
    chunk, 0.7 ms at 98,304 rows), both padded to whole chunks."""
    n = key.shape[0]
    first, rest = chunks
    _, order, gates = jax.lax.sort(
        (key, jnp.arange(n, dtype=jnp.int32), gate), num_keys=1,
        is_stable=True)
    pad = (0, first + -(-max(0, n - first) // rest) * rest - n)
    return jnp.pad(order, pad), jnp.pad(gates, pad)


def _forward(chunks, top_k, xt, gate, ws, key, ends, sizes):
    """``_held_experts`` and the sorted list it walked."""
    with jax.named_scope("moe_dispatch"):
        order, gates = _sorted_choices(chunks, key, gate)

    def run(rows, start, carry):
        y, done = carry
        with jax.named_scope("moe_dispatch"):
            _, tok, valid, group = _chunk_inputs(
                rows, top_k, xt.shape[0], order, ends, sizes, start)
            xs = _token_rows(xt, tok)
        out = _chunk_experts(xs, ws, group)
        with jax.named_scope("moe_combine"):
            # rows past the last held choice give nought (their output
            # may be undefined)
            out = jnp.where(valid[:, None], out.astype(jnp.float32), 0.0)
            g = jax.lax.dynamic_slice(gates, (start,), (rows,))
            return (y.at[tok].add(out * g[:, None]),
                    done + jnp.sum(valid.astype(jnp.int32)))

    zero = (jnp.zeros(xt.shape, jnp.float32), jnp.zeros((), jnp.int32))
    y, done = _over_chunks(chunks, ends[-1], run, zero)
    return (y.astype(xt.dtype), done), (order, gates)


# Differentiated by hand, so that the loop's length can follow the
# number of held choices, no chunk's tokens or weights are kept, and a
# chunk's rows are widened to float32 once in each direction, where they
# are added to their tokens.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(chunks, top_k, xt, gate, ws, key, ends, sizes):
    """-> (y [T, D], summed in float32 and returned in ``xt``'s dtype,
    so that its cotangent arrives in it and is gathered in it; rows
    processed).  ``chunks``: the rows of the first chunk and of each
    further one; ``ws``: the experts' weights, ``(w1, w3, w2)`` or
    ``(w1, w2)`` (``_chunk_hidden``); ``gate`` [N] float32 and ``key``
    [N], every choice's
    sort key (``_sorted_choices``); ``ends``/``sizes``: the held
    experts' groups in the sorted list."""
    return _forward(chunks, top_k, xt, gate, ws, key, ends, sizes)[0]


def _held_experts_fwd(chunks, top_k, xt, gate, ws, key, ends, sizes):
    out, (order, gates) = _forward(chunks, top_k, xt, gate, ws, key, ends,
                                   sizes)
    return out, (xt, ws, order, gates, ends, sizes)


def _held_experts_bwd(chunks, top_k, res, cotangent):
    xt, ws, order, gates, ends, sizes = res
    w2 = ws[-1]
    dy, _ = cotangent
    f32 = jnp.float32

    def run(rows, start, carry):
        dxt, dgate, dws = carry
        with jax.named_scope("moe_dispatch"):
            idx, tok, valid, group = _chunk_inputs(
                rows, top_k, xt.shape[0], order, ends, sizes, start)
            xs = _token_rows(xt, tok)
            dys = _token_rows(dy, tok)
        with jax.named_scope("moe_experts"):
            h, vjp = jax.vjp(
                lambda xs, *hidden: _chunk_hidden(xs, hidden, group),
                xs, *ws[:-1])
            # ``h @ w2`` itself is not made: its transpose in ``h``, on
            # the tokens' cotangent WITHOUT the gate, serves both the
            # hidden rows' cotangent and the gate's gradient
            u, = jax.linear_transpose(
                lambda h: jax.lax.ragged_dot(h, w2, group), h)(dys)
        with jax.named_scope("moe_combine"):
            # the gate is a scalar a row, so it multiplies after the
            # product, and on the F-wide side of both: a row's hidden
            # cotangent is ``g * u``, the gate's gradient ``h . u`` (the
            # row's output along its token's cotangent), ``w2``'s takes
            # ``g * h``: one pass over [rows, F], none over [rows, D];
            # the rows past the last held choice carry no gradient (a
            # padded row repeats choice 0)
            g = jax.lax.dynamic_slice(gates, (start,), (rows,))
            g = jnp.where(valid, g, 0.0)[:, None]
            dg = jnp.where(valid, jnp.sum(h.astype(f32) * u.astype(f32),
                                          axis=-1), 0.0)
            dh = (g * u).astype(h.dtype)
            gh = (g * h).astype(h.dtype)
        with jax.named_scope("moe_experts"):
            dw2, = jax.linear_transpose(
                lambda w2: jax.lax.ragged_dot(gh, w2, group), w2)(dys)
            dxs, *dhidden = vjp(dh)
        with jax.named_scope("moe_combine"):
            dxs = jnp.where(valid[:, None], dxs.astype(f32), 0.0)
            return (dxt.at[tok].add(dxs), dgate.at[idx].add(dg),
                    [a + d.astype(f32)
                     for a, d in zip(dws, (*dhidden, dw2))])

    zero = (jnp.zeros(xt.shape, f32), jnp.zeros((xt.shape[0] * top_k,), f32),
            [jnp.zeros(w.shape, f32) for w in ws])
    dxt, dgate, dws = _over_chunks(chunks, ends[-1], run, zero)
    return (dxt.astype(xt.dtype), dgate.astype(gates.dtype),
            tuple(d.astype(w.dtype) for d, w in zip(dws, ws)),
            None, None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


# Differentiated by hand: ``top_k``'s own transpose (and
# ``take_along_axis``'s) is a scatter of ``T * top_k`` scalars into
# [T, E], and the chip scatters or gathers a scalar in about 8 ns (2.3
# ms a layer at 32,768 tokens).  No (token, expert) pair is chosen
# twice, so a select and a sum over the slots give the same numbers.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _choose(probs, k, offset=None):
    """The ``k`` experts every token takes -- those with the largest
    ``probs`` [T, E], plus ``offset`` [E] or [T, E] where given (it only
    chooses: no gradient reaches it) -- and their ``probs``: ([T, k]
    float32, [T, k] int32)."""
    if offset is None:
        return tuple(jax.lax.top_k(probs, k))
    _, expert = jax.lax.top_k(probs + offset, k)
    hot = expert[..., None] == jnp.arange(probs.shape[-1])
    return jnp.sum(jnp.where(hot, probs[:, None, :], 0.0), axis=-1), expert


def _choose_fwd(probs, k, offset):
    chosen, expert = _choose(probs, k, offset)
    return (chosen, expert), (expert, jnp.arange(probs.shape[-1]), offset)


def _choose_bwd(k, res, cotangent):
    expert, experts, offset = res
    hot = expert[..., None] == experts
    d_probs = jnp.sum(jnp.where(hot, cotangent[0][..., None], 0.0), axis=-2)
    return d_probs, None if offset is None else jnp.zeros_like(offset)


_choose.defvjp(_choose_fwd, _choose_bwd)


def _group_offset(score, bias, n_group: int, topk_group: int):
    """What the sigmoid router adds to ``score`` [T, E] to choose: the
    ``bias`` [E] (where given), and ``-inf`` on every expert of a group
    the token does not keep.  The experts are ``n_group`` equal groups,
    each scored by the sum of its two largest ``score + bias``; a token
    keeps its ``topk_group`` best.  It only chooses: no gradient."""
    chooses = jax.lax.stop_gradient(score if bias is None else score + bias)
    tokens, n_experts = chooses.shape
    size = n_experts // n_group
    grouped = chooses.reshape(tokens, n_group, size)
    # the two largest by two maxima (a top-k is a sort on the chip)
    first = jnp.argmax(grouped, axis=-1)[..., None] == jnp.arange(size)
    best_two = jnp.max(grouped, axis=-1) + jnp.max(
        jnp.where(first, -jnp.inf, grouped), axis=-1)
    _, kept = jax.lax.top_k(best_two, topk_group)
    keep = jnp.any(kept[..., None] == jnp.arange(n_group), axis=-2)
    offset = jnp.where(jnp.repeat(keep, size, axis=-1), 0.0, -jnp.inf)
    return offset if bias is None else offset + bias


def moe_ffn(x: jax.Array, lp: Dict, top_k: int, norm_topk: bool = True,
            held: Tuple = None, scoring: str = "softmax",
            route_scale: float = 1.0, alike_tail: float = _ALIKE_TAIL,
            n_group: int = 1, topk_group: int = 1):
    """x [..., D] -> (y [..., D], stats); the residual is NOT included
    (nor the shared expert: ``shared_expert``).
    ``lp`` holds this layer's ``wr`` [D, E] and ``w1``/``w3``/``w2`` of
    the ``count`` experts ``held = (first, count)`` (all by default;
    ``first`` may be traced); without ``w3`` the experts are
    ``relu(x w1)^2 w2``; with ``w_down`` / ``w_up`` they work in that
    latent, the held share's sum projected up.  ``stats``: ``held_choices``,
    ``expert_load`` [count] (each held expert's number of choices),
    ``dropped_choices`` (held choices less the rows the chunks
    processed: 0 by construction), ``choices`` [..., top_k] (every
    token's experts, int32), and what ``balance_loss`` reads:
    ``router_load`` [E] (choices of every expert, held or not),
    ``router_prob`` [E] (float32 sum of the tokens' probabilities, the
    differentiable part) and ``tokens``.  The gates are the chosen
    scores (renormalised if ``norm_topk``) times ``route_scale``.
    ``scoring="sigmoid"``: the scores are sigmoids, the choice is by
    score plus ``lp["bias"]`` [E] (where the layer has one).
    ``alike_tail``: the share of blocks of alike tokens the first chunk
    may fall short of (``chunk_rows``).  ``n_group`` > 1 (sigmoid
    scores): the choice is within each token's ``topk_group`` best of
    ``n_group`` groups of experts (``_group_offset``)."""
    lead, D = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    n_experts = lp["wr"].shape[-1]
    count = lp["w1"].shape[0]
    first = 0 if held is None else held[0]
    if held is not None and held[1] != count:
        raise ValueError(f"{count} experts' weights for held={held}")
    N = T * top_k

    with jax.named_scope("moe_router"):
        # float32 in earnest: the chip's default for a float32 product
        # is one bfloat16 pass, which would move choices near a tie
        # (``moe_scores``: a cut point a rematerialised layer may keep,
        # ``models/remat.py``; as the shared expert's two products)
        logits = checkpoint_name(
            jnp.einsum("td,de->te", xt.astype(jnp.float32),
                       lp["wr"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST), "moe_scores")
        if scoring == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
            gate, expert = _choose(probs, top_k)               # [T, k]
            gate = checkpoint_name(gate, "moe_choice")
            if norm_topk:
                gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
            if route_scale != 1.0:
                gate = gate * route_scale
        elif scoring == "sigmoid":
            probs = jax.nn.sigmoid(logits)
            offset = lp.get("bias")
            if n_group > 1:
                offset = _group_offset(probs, offset, n_group, topk_group)
            gate, expert = _choose(probs, top_k, offset)
            gate = checkpoint_name(gate, "moe_choice")
            if norm_topk:
                gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
            gate = gate * route_scale
        else:
            raise ValueError(f"scoring {scoring!r}")
        # (one name for the chosen scores and the experts: kept, they
        # spare the backward pass the choice itself, and one without the
        # other spares nothing)
        expert = checkpoint_name(expert, "moe_choice")
        router_load = jnp.sum(jax.nn.one_hot(
            expert.reshape(N), n_experts, dtype=jnp.int32), axis=0)

    with jax.named_scope("moe_dispatch"):
        # Choice c = token * k + slot.  Held choices sort by their local
        # expert; the others get the key ``count`` and come last.
        local = expert.reshape(N) - first
        is_held = (local >= 0) & (local < count)
        key = jnp.where(is_held, local, count).astype(jnp.int32)
        sizes = jnp.sum(jax.nn.one_hot(key, count, dtype=jnp.int32), axis=0)
        ends = jnp.cumsum(sizes)                               # [count]
        chunks = chunk_rows(T, n_experts, count, top_k, alike_tail)

        if "w_down" in lp:          # the experts' latent (``moe_latent``)
            xt = checkpoint_name(jnp.einsum("td,dl->tl", xt, lp["w_down"]),
                                 "moe_latent")

    ws = tuple(lp[k] for k in ("w1", "w3", "w2") if k in lp)
    y, done = _held_experts(chunks, top_k, xt, gate.reshape(N), ws, key,
                            ends, sizes)
    if "w_up" in lp:
        with jax.named_scope("moe_combine"):
            # (``moe_latent_out``: kept, it spares the backward pass the
            # experts' forward, which ``w_up``'s gradient would make again)
            y = jnp.einsum("tl,ld->td", checkpoint_name(y, "moe_latent_out"),
                           lp["w_up"])
    stats = {"held_choices": ends[-1], "expert_load": sizes,
             "dropped_choices": jnp.sum(is_held.astype(jnp.int32)) - done,
             "choices": expert.astype(jnp.int32).reshape(*lead, top_k),
             "router_load": router_load,
             "router_prob": jnp.sum(probs, axis=0),
             "tokens": jnp.asarray(T, jnp.int32)}
    return y.reshape(*lead, D), stats


def balance_loss(stats: Dict) -> jax.Array:
    """The load-balance auxiliary of top-k routing (Switch's, over
    token-choices): ``E * sum_e f_e p_e`` with ``f_e`` the share of the
    token-choices routed to expert ``e`` and ``p_e`` the mean router
    probability of ``e``; 1 under a uniform router, larger the more the
    choices and the probabilities pile on the same experts.  Its
    gradient reaches the router through ``p``."""
    load = stats["router_load"].astype(jnp.float32)
    f = load / jnp.maximum(jnp.sum(load), 1.0)
    p = stats["router_prob"] / jnp.maximum(
        stats["tokens"].astype(jnp.float32), 1.0)
    return load.shape[0] * jnp.sum(f * p)


def shared_expert(x: jax.Array, lp: Dict) -> jax.Array:
    """The SwiGLU every token passes, on x [B, S, D]; without ``ws3``
    ``relu(x ws1)^2 ws2``."""
    with jax.named_scope("moe_shared"):
        if "ws3" not in lp:
            up = checkpoint_name(jnp.einsum("bsd,df->bsf", x, lp["ws1"]),
                                 "moe_shared_up")
            return jnp.einsum("bsf,fd->bsd", jnp.square(jax.nn.relu(up)),
                              lp["ws2"])
        gate = jax.nn.silu(checkpoint_name(
            jnp.einsum("bsd,df->bsf", x, lp["ws1"]), "moe_shared_gate"))
        up = checkpoint_name(jnp.einsum("bsd,df->bsf", x, lp["ws3"]),
                             "moe_shared_up")
        return jnp.einsum("bsf,fd->bsd", gate * up, lp["ws2"])


def shared_gate(x: jax.Array, lp: Dict) -> jax.Array:
    """``sigmoid(x . wsg)`` [B, S, 1] in ``x``'s dtype: what a gated
    shared expert's output is multiplied by, a token at a time."""
    with jax.named_scope("moe_shared"):
        return jax.nn.sigmoid(jnp.einsum(
            "bsd,do->bso", x, lp["wsg"],
            preferred_element_type=jnp.float32)).astype(x.dtype)


def update_bias(bias: jax.Array, load: jax.Array, rate: float) -> jax.Array:
    """The correction bias after a step: ``bias`` [layers, E] float32,
    ``load`` [layers, E] the step's choices of every expert (over all
    tokens: on a mesh, summed over the data axes).  An expert under the
    layer's mean load gains ``rate``, one over it loses ``rate``: signs
    of whole numbers (``sum - E * load``), so no rounding decides."""
    load = load.astype(jnp.int32)
    under = jnp.sum(load, axis=-1, keepdims=True) - load.shape[-1] * load
    return bias + rate * jnp.sign(under).astype(bias.dtype)


def counters(stats: Dict, with_load: bool = False) -> Dict:
    """A layer's ``stats`` as the float32 scalars a step reports:
    ``moe_held_choices``, ``moe_expert_load_max`` (the largest held
    expert's share of them), ``moe_dropped_choices`` and
    ``moe_balance_loss``.  ``with_load`` (a layer with a correction
    bias): also ``moe_load_cv``, the coefficient of variation of all
    experts' loads, and the loads themselves, ``moe_router_load`` [E],
    for ``update_bias``."""
    held = stats["held_choices"].astype(jnp.float32)
    more = {}
    if with_load:
        load = stats["router_load"].astype(jnp.float32)
        more = {"moe_load_cv": jnp.std(load) / jnp.maximum(
                    jnp.mean(load), 1e-20),
                "moe_router_load": stats["router_load"]}
    return {
        **more,
        "moe_held_choices": held,
        "moe_expert_load_max": jnp.max(stats["expert_load"]).astype(
            jnp.float32) / jnp.maximum(held, 1.0),
        "moe_dropped_choices": stats["dropped_choices"].astype(jnp.float32),
        "moe_balance_loss": balance_loss(stats),
    }


def moe_ffn_sharded(x: jax.Array, lp: Dict, top_k: int, norm_topk: bool,
                    mesh, **router):
    """The layer under an ``ep`` mesh axis: every shard holds
    ``E / ep`` experts, runs ``moe_ffn`` on its range for its own
    tokens (``dp`` x ``sp``), and the partial results are summed over
    ``ep``.  x [B, S, D].  The shared expert is not in here: it would
    be summed once a shard."""
    if "w3" not in lp or "w_down" in lp:
        raise ValueError("an ep mesh shares SwiGLU experts in the model's "
                         "width: no other form yet")
    count = lp["w1"].shape[0] // mesh.shape["ep"]
    tokens = ("dp", "sp")
    # the router's correction bias, where the layer has one: replicated
    bias = (lp["bias"],) if "bias" in lp else ()

    def shard(x, wr, w1, w3, w2, *bias):
        first = jax.lax.axis_index("ep") * count
        layer = {"wr": wr, "w1": w1, "w3": w3, "w2": w2}
        if bias:
            layer["bias"] = bias[0]
        y, stats = moe_ffn(x, layer, top_k, norm_topk, held=(first, count),
                           **router)
        everywhere = tokens + ("ep",)
        over = {"held_choices": everywhere, "dropped_choices": everywhere,
                # an expert's load is summed over the token shards; the
                # router is the same on every ep shard
                "expert_load": tokens, "router_load": tokens,
                "router_prob": tokens, "tokens": tokens}
        return jax.lax.psum(y, "ep"), dict(
            {k: jax.lax.psum(stats[k], axes) for k, axes in over.items()},
            choices=stats["choices"])

    experts = P("ep", None, None)
    placed = P("dp", "sp", None)
    return jax.shard_map(
        shard, mesh=mesh,
        in_specs=(placed, P(None, None), experts, experts, experts)
        + (P(None),) * len(bias),
        out_specs=(placed,
                   {"held_choices": P(), "dropped_choices": P(),
                    "expert_load": P("ep"), "router_load": P(),
                    "router_prob": P(), "tokens": P(), "choices": placed}),
        check_vma=False)(x, lp["wr"], lp["w1"], lp["w3"], lp["w2"], *bias)


def _init(key: jax.Array, n_layers: int, cfg, options: Dict) -> Dict:
    held = cfg.moe_experts_held or (0, cfg.moe_experts)
    return {"moe": init_moe_params(
        jax.random.fold_in(key, 8), n_layers, cfg.d_model,
        cfg.moe_d_ff or cfg.d_ff, cfg.moe_experts, held[1], cfg.dtype,
        cfg.moe_shared_width, cfg.moe_shared_gate, cfg.moe_act,
        cfg.moe_latent)}


def _specs(cfg, options: Dict) -> Dict:
    return {"moe": moe_param_specs(shared=cfg.moe_shared_width > 0,
                                   shared_gate=cfg.moe_shared_gate,
                                   act=cfg.moe_act, latent=cfg.moe_latent)}


def _moe_block(h, lp: Dict, call: LayerCall):
    """The expert layer on [B, S, D] -> (y, what it counted, None): with
    ``_init`` and ``_specs`` what reads the flat ``moe_*`` fields."""
    cfg, mesh, lp = call.cfg, call.mesh, lp["moe"]
    router = dict(scoring=cfg.moe_scoring, route_scale=cfg.moe_route_scale,
                  alike_tail=cfg.moe_alike_tail, n_group=cfg.moe_n_group,
                  topk_group=cfg.moe_topk_group)
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        if cfg.moe_experts_held is not None:
            raise ValueError("moe_experts_held is one chip's share; an "
                             "ep mesh shares the experts itself")
        y, stats = moe_ffn_sharded(h, lp, cfg.moe_top_k, cfg.moe_norm_topk,
                                   mesh, **router)
    else:
        y, stats = moe_ffn(h, lp, cfg.moe_top_k, cfg.moe_norm_topk,
                           held=cfg.moe_experts_held, **router)
    gated = {}
    if cfg.moe_shared_width:
        # once, whoever holds which experts
        shared = shared_expert(h, lp)
        if cfg.moe_shared_gate:
            gate = shared_gate(h, lp)
            shared = shared * gate
            gated["moe_shared_gate_mean"] = jnp.mean(gate.astype(jnp.float32))
        y = y + shared
    counted = {**counters(stats, with_load="bias" in lp), **gated}
    if cfg.moe_report_choices:
        counted["moe_choices"] = stats["choices"]
    return y, counted, None


MOE = LayerKind("moe", _init, _specs, _moe_block, needs="moe_experts")
