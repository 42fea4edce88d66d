"""Mamba-2's state-space rule (``ops/ssd.py``): the chunked ``jnp`` form
against the token-by-token recurrence it stands for, and the two fused
kernels (interpret mode) against the same recurrence, forward and every
gradient, under heads that forget within a position and heads that
hardly forget within the row, over more than one chunk and a last chunk
the row does not fill; the kernels on the layer's own operands (``x |
B | C`` and ``dt`` positions-minor, ``ssd_mixed``) and through the
probe's call (``ssd_rule``), which are one call; and the mixer's traced
step, in which the rule's glue is inside the kernels.

Tolerances.  Everything here is float32 on the CPU: the chunked form and
the kernels differ from the recurrence by summation order alone, under
1e-5 of the largest value at these sizes (1e-4 asked); a dropped or
misplaced term reads 1e-2 or more (``test_a_state_rounded_or_a_decay_
left_out_reads_far_off``).
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
            "mixed": jnp.resize(jnp.array([1e-3, 0.3, 12.0, 0.03]), h)}[decay]
    x = jax.random.normal(keys[0], (bsz, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (bsz, length, h)))
    b = jax.random.normal(keys[2], (bsz, length, groups, n)) * n ** -0.5
    c = jax.random.normal(keys[3], (bsz, length, groups, n)) * n ** -0.5
    return (x, dt, -rate, b, c), jax.random.normal(keys[4], (bsz, length,
                                                             h, p))


def _vjp(fn, args, dy):
    y, vjp = jax.vjp(fn, *args)
    return (y, *vjp(dy))


def _close(got, want, tol=1e-4, names=NAMES):
    for name, g, w in zip(("y",) + names, got, want):
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


def _laid_out(x, dt, b, c):
    """The rule's operands as a Mamba-2 layer holds them, positions
    minor: ``x | B | C`` [B, H P + 2 G N, L] and dt [B, H, L]."""
    bsz, length = x.shape[:2]
    mixed = jnp.concatenate([v.reshape(bsz, length, -1) for v in (x, b, c)],
                            axis=-1)
    return jnp.swapaxes(mixed, 1, 2), jnp.swapaxes(dt, 1, 2)


def _layers_call(x, dt, a, b, c, chunk):
    """``ssd_mixed`` as the layer calls it, the kernels in the
    interpreter -> y [B, L, H, P]."""
    mixed, dt_t = _laid_out(x, dt, b, c)
    y = ssd.ssd_mixed(mixed, dt_t, a, b.shape[2], b.shape[3], chunk=chunk,
                      use_pallas=True, interpret=True)
    return jnp.moveaxis(y, 3, 1)


@pytest.mark.parametrize("length,chunk,decay,h,groups", [
    (256, 16, "mixed", 4, 2),   # two blocks of 8 chunks
    (120, 16, "slow", 4, 1),    # a ragged last chunk; 4 heads read a group
    (200, 16, "mixed", 8, 2),   # 13 chunks, a block each, the last ragged
])
def test_kernels_on_the_layers_operands_are_the_recurrence(length, chunk,
                                                           decay, h, groups):
    """The kernels reading ``x``, ``B``, ``C`` out of the convolution's
    output and ``dt`` as the projection leaves it: ``y`` and ``dx``,
    ``ddt``, ``dA_log`` (``a = -exp(A_log)``, as the layer makes it),
    ``dB``, ``dC``."""
    (x, dt, a, b, c), dy = inputs(11, length, decay, h=h, groups=groups)
    args = (x, dt, jnp.log(-a), b, c)
    got = _vjp(lambda x, dt, a_log, b, c: _layers_call(
        x, dt, -jnp.exp(a_log), b, c, chunk), args, dy)
    want = _vjp(lambda x, dt, a_log, b, c: recurrence(
        x, dt, -jnp.exp(a_log), b, c), args, dy)
    _close(got, want, names=("x", "dt", "A_log", "b", "c"))


def test_the_probes_call_is_the_layers_call_bit_for_bit():
    """``ssd_rule(x, dt, a, b, c)`` -- what the benchmark's probe takes
    ``jax.vjp`` of -- runs the layer's own call: the same ``y`` and the
    same five gradients, bit for bit."""
    args, dy = inputs(13, 64, "mixed")
    got = _vjp(lambda *v: ssd.ssd_rule(*v, chunk=16, use_pallas=True,
                                       interpret=True), args, dy)
    want = _vjp(lambda *v: _layers_call(*v, chunk=16), args, dy)
    for name, g, w in zip(("y",) + NAMES, got, want):
        assert g.dtype == w.dtype and bool(jnp.array_equal(g, w)), name


def _outside_the_kernels(jaxpr):
    """Every equation of ``jaxpr`` and its sub-jaxprs that no
    ``pallas_call`` holds, with the jaxpr it is in; and the names of the
    ``pallas_call``s."""
    eqns, kernels = [], []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.append(eqn.params["name"])
                continue
            eqns.append((eqn, jaxpr))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [
                        value]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jaxpr)
    return eqns, kernels


def test_the_mixers_glue_is_inside_the_kernels(monkeypatch):
    """A Mamba-2 mixer's forward and backward (rematerialised as the
    step's layers are) traced with the kernels on, as on a TPU: outside
    the two ``pallas_call``s there is no running sum, no product of x by
    dt and no transpose that splits heads off or takes x, B or C apart
    from the others -- each one left moves the positions of a whole
    array past its channels, which XLA lays out as a bitcast
    (``tests/test_flash_attention.py`` compiles the mixer for the chip
    and finds no copy of them)."""
    import types
    from ray_tpu.models import mamba2, remat
    from ray_tpu.models.common import LayerCall
    heads, p, n_state, seq, width = 4, 64, 128, 384, 32
    m = mamba2.Mamba2Config(num_heads=heads, head_dim=p, n_groups=1,
                            state_size=n_state, chunk=128)
    cfg = types.SimpleNamespace(mamba2=m, d_model=width, dtype=jnp.float32,
                                norm_eps=1e-5)
    lp = jax.tree.map(lambda v: v[0], mamba2._init(
        jax.random.PRNGKey(0), 1, cfg, {}))
    layer = jax.checkpoint(
        lambda h, lp: mamba2._mamba2(h, lp, LayerCall(cfg, "mamba2"))[0],
        policy=jax.checkpoint_policies.save_only_these_names(
            *remat.BASE_NAMES))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jaxpr = jax.make_jaxpr(jax.grad(lambda h, lp: jnp.sum(
        jnp.square(layer(h, lp))), (0, 1)))(
            jnp.ones((1, seq, width)), lp).jaxpr
    eqns, kernels = _outside_the_kernels(jaxpr)
    assert kernels.count("ssd_fwd") == 1 and kernels.count("ssd_bwd") == 1
    names = [eqn.primitive.name for eqn, _ in eqns]
    assert not [n for n in names if "cum" in n], names
    x_size, dt_size = seq * heads * p, seq * heads
    made = {}
    for eqn, where in eqns:
        for var in eqn.outvars:
            made[(id(where), var)] = eqn
    for eqn, where in eqns:
        if eqn.primitive.name == "mul" and \
                eqn.outvars[0].aval.size == x_size:
            for v in eqn.invars:
                src = made.get((id(where), v)) if hasattr(v, "count") \
                    else None
                assert not (src is not None
                            and src.primitive.name == "broadcast_in_dim"
                            and src.invars[0].aval.size == dt_size), eqn
        shape = eqn.invars[0].aval.shape if eqn.invars else ()
        if eqn.primitive.name == "transpose" and seq in shape:
            # an activation's: no heads split off, not B or C alone
            assert len(shape) == 3 and n_state not in shape, eqn
