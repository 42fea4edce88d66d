"""The gated delta rule: the chunked ``jnp`` form against the recurrence
it stands for, the two fused kernels (interpret mode) against the chunked
form, and a row that holds two documents.

Tolerances.  Everything here is float32 on the CPU: the chunked form and
the recurrence differ by summation order and by the inverse's products,
1e-5 of the largest value at these sizes (5e-5 asked).  The kernels make
the chunked form's products, but no longer in its order: the inverse's
16-row blocks ride one block-diagonal operand, ``dS`` takes ``O``'s term
unrounded, the backward is written out by hand and sums each gradient's
parts in its own order.  Read over every case below: 8.3e-7 of the
largest value at most (``dg`` under mixed decays), 5e-6 asked; a dropped
or misplaced term reads 1e-2 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import gated_delta
from ray_tpu.ops.gated_delta import gated_delta_rule

NAMES = ("q", "k", "v", "g", "beta")


def recurrence(q, k, v, g, beta):
    """Token by token, as the rule is written: [B, L, H, D] in float32."""
    def head(q, k, v, g, beta):                     # [L, D], [L]
        def step(s, x):
            q, k, v, g, beta = x
            s = jnp.exp(g) * s
            s = s + jnp.outer(k, beta * (v - s.T @ k))
            return s, s.T @ q
        zero = jnp.zeros((q.shape[-1], v.shape[-1]), jnp.float32)
        return jax.lax.scan(step, zero, (q, k, v, g, beta))[1]

    over_heads = jax.vmap(head, in_axes=(1, 1, 1, 1, 1), out_axes=1)
    with jax.default_matmul_precision("highest"):
        return jax.vmap(over_heads)(q, k, v, g, beta)


def inputs(seed, length, decay, b=2, h=3, dk=16, dv=8, hk=None):
    """q and k of unit length (q scaled as the layer scales it; ``hk``
    heads of them, ``h`` where it is not given), ``g`` from a per-head
    rate: ``decay`` "fast" forgets within a position or two, "slow"
    hardly within the row, "mixed" has heads of each."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    hk = hk or h
    q = unit(jax.random.normal(keys[0], (b, length, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (b, length, hk, dk)))
    v = jax.random.normal(keys[2], (b, length, h, dv))
    rate = {"fast": jnp.full((h,), 12.0), "slow": jnp.full((h,), 1e-3),
            "mixed": jnp.array([1e-3, 0.3, 12.0, 0.03])[:h]}[decay]
    g = -rate * jax.nn.softplus(jax.random.normal(keys[3], (b, length, h)))
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(keys[4], (b, length, h)))
    return (q, k, v, g, beta), jax.random.normal(keys[5], (b, length, h, dv))


def close(a, b, tol):
    scale = float(jnp.max(jnp.abs(b))) + 1e-30
    np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale,
                               atol=tol, rtol=0)


@pytest.mark.parametrize("length", [64, 128, 320])
@pytest.mark.parametrize("decay", ["fast", "slow", "mixed"])
def test_chunked_is_the_recurrence_values_and_all_five_gradients(length,
                                                                 decay):
    args, dout = inputs(length, length, decay)
    chunked = lambda *a: gated_delta_rule(*a, use_pallas=False)
    close(chunked(*args), recurrence(*args), 5e-5)
    want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * dout),
                    argnums=range(5))(*args)
    got = jax.grad(lambda *a: jnp.sum(chunked(*a) * dout),
                   argnums=range(5))(*args)
    for name, a, b in zip(NAMES, got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        close(a, b, 5e-5)


@pytest.mark.parametrize("key_heads", [4, 2], ids=["Hk=H", "Hk=H/2"])
@pytest.mark.parametrize("decay", ["fast", "slow", "mixed"])
@pytest.mark.parametrize("length,chunk", [(64, 64), (320, 64), (512, 16),
                                          (128, 32), (1024, 64), (256, 64),
                                          (384, 64), (192, 64)])
def test_both_kernels_interpreted_are_the_chunked_form(length, chunk, decay,
                                                       key_heads):
    """Values, all five gradients (by hand in ``gated_delta_bwd``) and
    the counter's state: one chunk, five chunks a block of one, 32 chunks
    of 16 rows in blocks of 8, four chunks of two halves; then chunks of
    64 rows whose count gives grid steps of 8 (two steps of four tiles:
    the cell's arrangement), 4, 2 and 1 chunks, the backward kernel
    walking a step's states from the one it is handed; q and k with a
    head a value head and with one for two."""
    n = length // chunk
    assert gated_delta._chunks_a_step(n) == {
        1: 1, 5: 1, 32: 8, 4: 4, 16: 8, 6: 2, 3: 1}[n]
    args, dout = inputs(7 + length, length, decay, h=4, hk=key_heads)
    chunked = lambda *a: gated_delta_rule(*a, chunk=chunk, use_pallas=False,
                                          with_state=True)
    kernels = lambda *a: gated_delta_rule(*a, chunk=chunk, use_pallas=True,
                                          interpret=True, with_state=True)
    for a, b in zip(kernels(*args), chunked(*args)):
        close(a, b, 5e-6)
    want = jax.grad(lambda *a: jnp.sum(chunked(*a)[0] * dout),
                    argnums=range(5))(*args)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a)[0] * dout),
                   argnums=range(5))(*args)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and bool(jnp.all(jnp.isfinite(a))), name
        close(a, b, 5e-6)


@pytest.mark.parametrize("length,step", [(1024, 8), (256, 4), (384, 2),
                                         (192, 1)])
def test_the_forward_writes_every_step_th_entering_state(length, step,
                                                         monkeypatch):
    """What ``gated_delta_fwd`` hands ``gated_delta_bwd``: the state
    entering each grid step, which is every ``step``-th entering state
    of the chunked form's scan over chunks; the last of the scan's is the
    counter's."""
    (q, k, v, g, beta), _ = inputs(31 + length, length, "mixed", h=4, hk=2)
    scanned = []
    scan = gated_delta._state_pass_scan

    def kept(*operands):
        scanned.append(scan(*operands))
        return scanned[-1]
    monkeypatch.setattr(gated_delta, "_state_pass_scan", kept)
    gated_delta._chunked_rule(q, k, v, g, beta, 64)
    (states, _), = scanned                          # [B H, N, Dk, Dv]

    heads_first = lambda x: jnp.moveaxis(x, 2, 1).reshape(
        -1, length, x.shape[-1])
    rows = lambda x: jnp.moveaxis(x, 2, 1).reshape(-1, length // 64, 64)
    _, steps, last = gated_delta._kernel_forward(
        heads_first(q), heads_first(k), heads_first(v),
        jnp.cumsum(rows(g), axis=-1), rows(beta), 64, True)
    assert steps.shape == (2 * 4, length // 64 // step, 16, 8)
    assert steps.dtype == last.dtype == jnp.float32
    close(steps, states[:, ::step], 5e-6)
    close(last, states[:, -1], 5e-6)


@pytest.mark.parametrize("how", [dict(use_pallas=False),
                                 dict(use_pallas=True, interpret=True)],
                         ids=["chunked", "kernels"])
def test_the_grouped_call_is_the_repeated_one(how):
    """q and k at half of v's heads: what the call with each key head
    written out twice gives, dq and dk summed over a key head's two
    value heads."""
    (q, k, v, g, beta), dout = inputs(21, 192, "mixed", h=4, hk=2)
    rule = lambda *a: gated_delta_rule(*a, **how)
    twice = lambda x: jnp.repeat(x, 2, axis=2)
    close(rule(q, k, v, g, beta), rule(twice(q), twice(k), v, g, beta), 5e-6)
    grouped = jax.grad(lambda *a: jnp.sum(rule(*a) * dout),
                       argnums=range(5))(q, k, v, g, beta)
    repeated = jax.grad(lambda *a: jnp.sum(rule(*a) * dout),
                        argnums=range(5))(twice(q), twice(k), v, g, beta)
    for name, a, b in zip(NAMES, grouped, repeated):
        if name in "qk":
            b = b.reshape(*a.shape[:2], 2, 2, a.shape[-1]).sum(axis=3)
        close(a, b, 5e-6)
    with pytest.raises(ValueError, match="2 / 2 heads of q / k for 3"):
        rule(q, k, *(x[:, :, :3] for x in (v, g, beta)))


def test_remat_runs_the_forward_kernel_once_where_its_names_are_kept():
    """One forward kernel, whoever asks: it writes ``o`` and the state
    entering each grid step, never a state a chunk.  Plain ``grad`` runs
    it once beside ``gated_delta_bwd``; so does a ``jax.checkpoint``
    whose policy saves ``RESIDUAL_NAMES`` (what ``remat_layer`` always
    does); one that saves nothing runs it again in the backward pass.
    The gradients are the same three ways."""
    args, dout = inputs(9, 1024, "mixed")
    every_chunk = (2 * 3, 1024 // 64, 16, 8)
    steps = (2 * 3, 1024 // 64 // 8, 16, 8)

    def loss(*a):
        return jnp.sum(gated_delta_rule(*a, use_pallas=True,
                                        interpret=True) * dout)

    def calls(fn):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    shapes = [v.aval.shape for v in eqn.outvars]
                    assert every_chunk not in shapes
                    found.append((eqn.params["name"], steps in shapes))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return sorted(found)

    once = [("gated_delta_bwd", False), ("gated_delta_fwd", True)]
    assert calls(loss) == once[1:]
    plain = jax.grad(loss, argnums=range(5))
    assert calls(plain) == once
    named = jax.grad(jax.checkpoint(
        loss, policy=jax.checkpoint_policies.save_only_these_names(
            *gated_delta.RESIDUAL_NAMES)), argnums=range(5))
    assert calls(named) == once
    nothing = jax.grad(jax.checkpoint(loss), argnums=range(5))
    assert calls(nothing) == once + once[1:]
    want = plain(*args)
    for got in (named(*args), nothing(*args)):
        for a, b in zip(got, want):
            close(a, b, 1e-7)


def test_the_rules_residuals_carry_their_names_forward_and_differentiated():
    """``o`` and the step states are named in the ``fwd`` rule, for a
    checkpoint's policy, and in the primal function, for whoever reads
    the forward's jaxpr alone (``models/remat.py``)."""
    args, dout = inputs(4, 128, "slow")

    def loss(*a):
        return jnp.sum(gated_delta_rule(*a, use_pallas=True,
                                        interpret=True) * dout)

    for fn in (loss, jax.grad(loss, argnums=range(5))):
        text = str(jax.make_jaxpr(fn)(*args))
        for name in gated_delta.RESIDUAL_NAMES:
            assert f"name[name={name}]" in text, name
    assert gated_delta.RESIDUAL_NAMES == ("delta_out", "delta_step_states")


def test_the_kernels_carry_their_names_into_the_traced_program():
    args, dout = inputs(3, 128, "mixed")
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(gated_delta_rule(
        *a, use_pallas=True) * dout), argnums=range(5)))(*args))
    assert "gated_delta_fwd" in text and "gated_delta_bwd" in text


def test_bfloat16_operands_stay_near_the_float32_rule():
    """The operands' rounding only: the state and the inverse stay
    float32 (a state kept in bfloat16 is 10 times further off)."""
    args, _ = inputs(11, 256, "mixed")
    want = recurrence(*args)
    low = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    got = gated_delta_rule(*low, use_pallas=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    close(got.astype(jnp.float32), want, 2e-2)


def test_the_state_crosses_a_separator():
    """A row of two documents is neither document alone: the second
    half's outputs see the state the first half left (as attention sees
    its keys); the first half is what it is alone."""
    (q, k, v, g, beta), _ = inputs(5, 128, "slow")
    run = lambda s: gated_delta_rule(q[:, s], k[:, s], v[:, s], g[:, s],
                                     beta[:, s], use_pallas=False)
    whole = run(slice(0, 128))
    first, second = run(slice(0, 64)), run(slice(64, 128))
    close(whole[:, :64], first, 5e-6)
    assert float(jnp.max(jnp.abs(whole[:, 64:] - second))) > 1e-2 * float(
        jnp.max(jnp.abs(second)))


def _kernel_inverse(a):
    """``gated_delta._kernel_inverse`` on one tile, interpreted."""
    from jax.experimental import pallas as pl
    n = a.shape[0]

    def kernel(a_ref, t_ref):
        m = gated_delta._tile_masks(n, n, n)
        t_ref[...] = gated_delta._kernel_inverse(a_ref[...], m.rows, m.cols,
                                                 n)
    return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
        (n, n), jnp.float32), interpret=True)(a)


@pytest.mark.parametrize("inverse,n", [
    (gated_delta.unit_lower_inverse, 64), (_kernel_inverse, 64),
    (_kernel_inverse, 32), (_kernel_inverse, 16), (_kernel_inverse, 8)],
    ids=["jnp-64", "kernel-64", "kernel-32", "kernel-16", "kernel-8"])
def test_the_inverse_is_the_inverse_where_rows_are_alike(inverse, n):
    """Identical keys and strong writes: the strictly lower block is all
    ones, whose powers reach 1e17 at 64 rows; the halved inverse, as
    ``jnp`` and as the kernels make it on one block-diagonal tile, is the
    bidiagonal one to rounding, and on a random block the inverse."""
    a = jnp.tril(jnp.ones((n, n), jnp.float32), -1)
    want = jnp.eye(n) - jnp.eye(n, k=-1)
    np.testing.assert_allclose(np.asarray(inverse(a)), np.asarray(want),
                               atol=1e-3)
    a = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (n, n)), -1)
    close(inverse(a), jnp.linalg.inv(jnp.eye(n) + a), 1e-5)


def test_the_inverses_gradient_by_hand_is_autodiffs():
    n = 64
    a = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (2, n, n)),
                       -1)
    dt = jax.random.normal(jax.random.PRNGKey(1), (2, n, n))
    by_hand = jax.grad(lambda a: jnp.sum(
        gated_delta.unit_lower_inverse(a) * dt))(a)
    by_jax = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(
        jnp.eye(n) + a) * dt))(a)
    close(by_hand, by_jax, 1e-4)


def test_a_row_that_is_no_whole_number_of_chunks_is_refused():
    args, _ = inputs(1, 96, "slow")
    with pytest.raises(ValueError, match="chunks of 64"):
        gated_delta_rule(*args, use_pallas=False)
    # the chunked form halves any chunk; the kernels' masks want a power
    # of two above 16 rows
    assert gated_delta_rule(*args, chunk=48, use_pallas=False).shape \
        == args[2].shape
    with pytest.raises(ValueError, match="48 is no power of two"):
        gated_delta_rule(*args, chunk=48, use_pallas=True, interpret=True)
