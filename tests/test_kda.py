"""Kimi Delta Attention's rule (``ops/kda.py``): the chunked ``jnp`` form
against the recurrence it stands for, and the two fused kernels
(interpret mode) against both, forward and all five gradients.

Tolerances.  Everything here is float32 on the CPU.  The chunked form
and the recurrence differ by summation order, by the inverse's products
and by the decays factored about each 16-row block's first row: 4e-6 of
the largest value read where the channels' decays are mixed, 5e-5
asked.  Where every channel forgets at the gate's bound (``g = -5``) the
factors span ``exp(+-75)`` and JAX's gradient of the chunked form sums
terms of that spread into ``dg``, whose true values are small there
(5e-4): its norm is off by 1.1e-2 read, the kernels' hand-written
gradient by 1.1e-4 (2e-3 asked of them, 3e-2 of the ``jnp`` form); every
other part 6e-6.  A dropped or misplaced term reads 1e-1 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda
from ray_tpu.ops.kda import kda_rule


def recurrence(q, k, v, g, beta):
    """Token by token, as the rule is written: [B, L, H, D] in float32."""
    def head(q, k, v, g, beta):                     # [L, D], [L]
        def step(s, x):
            q, k, v, g, beta = x
            s = jnp.exp(g)[:, None] * s
            s = s + jnp.outer(k, beta * (v - s.T @ k))
            return s, s.T @ q
        zero = jnp.zeros((q.shape[-1], v.shape[-1]), jnp.float32)
        return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]

    over_heads = jax.vmap(head, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(over_heads)(q, k, v, g, beta)


def inputs(seed, length, decay, b=1, h=2, dk=128, dv=128):
    """q and k of unit length (q scaled as the layer scales it), ``g``
    in the gate's range: ``decay`` "mixed" draws every channel's rate
    between the bound and nought, the last head never forgetting;
    "floor" holds every channel of every position at the bound."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(keys[0], (b, length, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, length, h, dk)))
    v = jax.random.normal(keys[2], (b, length, h, dv))
    if decay == "floor":
        g = jnp.full((b, length, h, dk), kda.LOWER)
    else:
        g = kda.LOWER * jax.nn.sigmoid(
            3.0 * jax.random.normal(keys[3], (b, length, h, dk)) - 2.0)
        g = g.at[:, :, -1].set(0.0)
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (b, length, h)))
    return (q, k, v, g, beta), jax.random.normal(keys[5], (b, length, h, dv))


def _vjp(fn, args, do):
    o, back = jax.vjp(fn, *args)
    return (o, *back(do))


def _oracle(*args, chunk=kda.CHUNK):
    return kda_rule(*args, chunk=chunk, use_pallas=False)


def _kernels(*args):
    return kda_rule(*args, use_pallas=True, interpret=True)


NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


def _close(got, want, tol, what):
    for name, a, b in zip(NAMES, got, want):
        gap = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert gap <= tol.get(name, tol["*"]), (what, name, gap)


def _norm_gap(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def mixed():
    args, do = inputs(0, 256, "mixed")
    return args, do, _vjp(recurrence, args, do), _vjp(_oracle, args, do)


def test_the_chunked_form_is_the_recurrence(mixed):
    _, _, ref, oracle = mixed
    _close(oracle, ref, {"*": 5e-5}, "mixed")


def test_both_kernels_interpreted_are_the_chunked_form(mixed):
    """One grid step of four chunks each way (256 positions), two tiles
    of two chunks, every off-diagonal pair of 16-row blocks, a head that
    never forgets."""
    args, do, ref, oracle = mixed
    kernels = _vjp(_kernels, args, do)
    _close(kernels, oracle, {"*": 5e-5}, "kernels")
    _close(kernels, ref, {"*": 5e-5}, "kernels against the recurrence")


@pytest.mark.parametrize("chunks", [8, 3, 1])
def test_the_kernels_tiles_are_the_chunked_form_and_the_recurrence(chunks):
    """The tile a row's chunk count gives: 8 chunks are two grid steps
    of two 128-row tiles (the state handed from one grid step to the
    next, each way); 3, an odd count, and 1 run a chunk of 64 rows a
    tile.  Forward and all five gradients."""
    args, do = inputs(5, chunks * kda.CHUNK, "mixed", dk=64, dv=64)
    assert kda._layout(chunks * kda.CHUNK, kda.CHUNK)[2] == (
        2 * kda.CHUNK if chunks % 2 == 0 else kda.CHUNK)
    kernels = _vjp(_kernels, args, do)
    _close(kernels, _vjp(_oracle, args, do), {"*": 5e-5}, "kernels")
    _close(kernels, _vjp(recurrence, args, do), {"*": 5e-5},
           "kernels against the recurrence")


def test_every_channel_at_the_gates_bound_stays_finite_and_right():
    """g = -5 everywhere: a block's right factors reach exp(75), still
    below float32's largest value."""
    args, do = inputs(1, 128, "floor")
    ref = _vjp(recurrence, args, do)
    for fn, dg_tol in ((_oracle, 3e-2), (_kernels, 2e-3)):
        got = _vjp(fn, args, do)
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
        _close(got, ref, {"*": 5e-5, "dg": 1.0}, fn.__name__)
        assert _norm_gap(got[4], ref[4]) <= dg_tol, fn.__name__


def test_a_decay_a_head_or_a_rounded_state_reads_far_off(mixed):
    """What the cell's controls change, at this size: one decay a head
    (the mean of its channels') or the state in bfloat16 moves the
    output far past the tolerance above."""
    args, _, ref, _ = mixed
    q, k, v, g, beta = args
    per_head = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    assert _norm_gap(_oracle(q, k, v, per_head, beta), ref[0]) > 1e-2
    half = _oracle(*(x.astype(jnp.bfloat16) for x in (q, k, v)), g, beta)
    assert _norm_gap(half.astype(jnp.float32), ref[0]) > 1e-3


def test_the_rules_residuals_carry_their_names():
    args, _ = inputs(2, 64, "mixed")
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(_kernels, *a)[0])(*args))
    for name in kda.RESIDUAL_NAMES:
        assert name in text


def test_a_row_that_is_no_whole_number_of_chunks_is_refused():
    args, _ = inputs(3, 80, "mixed", dk=16, dv=16)
    with pytest.raises(ValueError, match="pad upstream"):
        kda_rule(*args, chunk=64, use_pallas=False)
    with pytest.raises(ValueError, match="one key head a value head"):
        kda_rule(*args[:3], args[3][..., :8], args[4], chunk=16,
                 use_pallas=False)


def test_the_state_crosses_the_row_and_starts_at_nought():
    """A row's second half read alone starts from an empty state; read
    after the first half it does not, where the decays let it carry."""
    args, _ = inputs(4, 64, "mixed", dk=16, dv=16)
    whole = np.asarray(_oracle(*args))
    tail = np.asarray(_oracle(*(x[:, 32:] for x in args), chunk=16))
    head = np.asarray(_oracle(*(x[:, :32] for x in args), chunk=16))
    np.testing.assert_allclose(whole[:, :32], head, rtol=1e-5, atol=1e-6)
    assert np.abs(whole[:, 32:] - tail).max() > 1e-3
