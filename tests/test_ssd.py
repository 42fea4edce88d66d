"""Mamba-2's state-space rule (``ops/ssd.py``): the chunked ``jnp`` form
against the token-by-token recurrence it stands for, and the two fused
kernels (interpret mode) against the same recurrence, forward and every
gradient, under heads that forget within a position and heads that
hardly forget within the row, over more than one chunk and a last chunk
the row does not fill.

Tolerances.  Everything here is float32 on the CPU: the chunked form and
the kernels differ from the recurrence by summation order alone, under
1e-5 of the largest value at these sizes (1e-4 asked); a dropped or
misplaced term reads 1e-2 or more (the last test).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd

NAMES = ("x", "dt", "a", "b", "c")


def recurrence(x, dt, a, b, c):
    """Token by token, as the rule is written: x [B, L, H, P], dt [B, L,
    H], a [H], b, c [B, L, G, N] -> y [B, L, H, P], float32."""
    r = x.shape[2] // b.shape[2]
    b, c = (jnp.repeat(v, r, axis=2) for v in (b, c))

    def head(x, dt, a, b, c):                       # [L, P], [L], [], [L, N]
        def step(s, xs):
            x, dt, b, c = xs
            s = jnp.exp(dt * a) * s + jnp.outer(b, dt * x)      # [N, P]
            return s, c @ s
        zero = jnp.zeros((b.shape[-1], x.shape[-1]), jnp.float32)
        return jax.lax.scan(step, zero, (x, dt, b, c))[1]

    over_heads = jax.vmap(head, in_axes=(1, 1, 0, 1, 1), out_axes=1)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(over_heads, in_axes=(0, 0, None, 0, 0))(
            x, dt, a, b, c)


def inputs(seed, length, decay, bsz=2, h=4, groups=2, p=8, n=16):
    """``decay`` "fast": every head forgets within a position or two;
    "slow": hardly within the row; "mixed": heads of both and between."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    rate = {"fast": jnp.full((h,), 12.0), "slow": jnp.full((h,), 1e-3),
            "mixed": jnp.array([1e-3, 0.3, 12.0, 0.03])[:h]}[decay]
    x = jax.random.normal(keys[0], (bsz, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (bsz, length, h)))
    b = jax.random.normal(keys[2], (bsz, length, groups, n)) * n ** -0.5
    c = jax.random.normal(keys[3], (bsz, length, groups, n)) * n ** -0.5
    return (x, dt, -rate, b, c), jax.random.normal(keys[4], (bsz, length,
                                                             h, p))


def _vjp(fn, args, dy):
    y, vjp = jax.vjp(fn, *args)
    return (y, *vjp(dy))


def _close(got, want, tol=1e-4):
    for name, g, w in zip(("y",) + NAMES, got, want):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        err = float(jnp.max(jnp.abs(g - w))) / scale
        assert err <= tol, (name, err)


@pytest.mark.parametrize("length,chunk,decay", [
    (64, 16, "mixed"), (50, 16, "slow"), (48, 16, "fast"), (40, 64, "mixed")])
def test_chunked_form_is_the_recurrence(length, chunk, decay):
    """Forward and all five gradients; 50 and 40 leave the last chunk
    short of full (40 is less than one chunk)."""
    args, dy = inputs(3, length, decay)
    got = _vjp(lambda *a: ssd.ssd_rule(*a, chunk=chunk, use_pallas=False),
               args, dy)
    _close(got, _vjp(recurrence, args, dy))


@pytest.mark.parametrize("length,chunk,decay,groups", [
    (64, 16, "mixed", 2), (56, 16, "slow", 1), (32, 16, "fast", 4),
    (128, 16, "mixed", 2)])
def test_kernels_are_the_recurrence(length, chunk, decay, groups):
    """The two kernels in the Pallas interpreter: more than one block of
    chunks (128 / 16 = 8 chunks, a block of 8), a ragged last chunk
    (56), one head a group and every head reading one group."""
    args, dy = inputs(5, length, decay, groups=groups)
    got = _vjp(lambda *a: ssd.ssd_rule(*a, chunk=chunk, use_pallas=True,
                                       interpret=True), args, dy)
    _close(got, _vjp(recurrence, args, dy))


def test_a_state_rounded_or_a_decay_left_out_reads_far_off():
    """What the tolerance above has to catch: the recurrence with its
    decay left out, or the kernels' output under another rate."""
    args, dy = inputs(7, 64, "mixed")
    want = _vjp(recurrence, args, dy)
    x, dt, a, b, c = args
    undecayed = _vjp(recurrence, (x, dt, jnp.zeros_like(a), b, c), dy)
    with pytest.raises(AssertionError):
        _close(undecayed, want, tol=1e-2)
    other = _vjp(lambda *v: ssd.ssd_rule(*v, chunk=16, use_pallas=True,
                                         interpret=True),
                 (x, dt, a * 1.01, b, c), dy)
    with pytest.raises(AssertionError):
        _close(other, want, tol=1e-4)


def test_the_state_crosses_the_row_and_starts_at_nought():
    """A row's second half, run alone, differs from the same positions
    run after the first half: the state is the row's, whatever it
    holds; the first position's output is ``C_0 B_0^T dt_0 x_0``."""
    (x, dt, a, b, c), _ = inputs(9, 32, "slow", bsz=1)
    whole = ssd.ssd_rule(x, dt, a, b, c, chunk=16, use_pallas=False)
    half = ssd.ssd_rule(*(v[:, 16:] for v in (x, dt)), a,
                        *(v[:, 16:] for v in (b, c)), chunk=16,
                        use_pallas=False)
    assert float(jnp.max(jnp.abs(whole[:, 16:] - half))) > 1e-2
    first = jnp.einsum("bgn,bgn->bg", c[:, 0], b[:, 0])
    want = jnp.repeat(first, 2, axis=1)[..., None] * dt[:, 0, :, None] \
        * x[:, 0]
    np.testing.assert_allclose(whole[:, 0], want, rtol=1e-5, atol=1e-6)
