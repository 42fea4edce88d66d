"""The selective scan: the two kernels (interpret mode) and the chunked
``jnp`` path against the token-by-token recurrence it stands for,
forward and every gradient, across chunk boundaries.

Tolerances.  Everything here is float32 on the CPU.  The chunked path IS
the recurrence under a rematerialised scan over chunks: it differs by
nothing forward and by summation order backward (``dA`` and ``D`` are
sums over positions), 4e-7 of a gradient's norm read, 5e-6 asked.  The
kernels make the recurrence's products in the recurrence's order
forward (1e-6 of the largest value read, 5e-6 asked) and by hand
backward: ``dB`` and ``dC`` are summed over channels in the kernel's own
order (tiles first, lanes last) and ``dA`` over chunks, 2.1e-7 of the
gradient's norm read, 5e-6 asked; a dropped or misplaced term reads
1e-2 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import selective_scan as ss
from ray_tpu.ops.selective_scan import selective_scan

NAMES = ("c", "delta", "A", "B", "C", "D")


def inputs(seed, length, rate, rows=2, channels=1024, states=4):
    """``delta . A`` a position: ``rate`` "fast" forgets within a
    position or two, "slow" hardly within the row, "mixed" has channels
    of each (spaced evenly in the logarithm)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    scale = {"fast": jnp.full((channels,), 4.0),
             "slow": jnp.full((channels,), 1e-3),
             "mixed": jnp.exp(jnp.linspace(jnp.log(1e-4), 0.0, channels))
             }[rate]
    c = jax.random.normal(keys[0], (rows, length, channels))
    delta = scale * jax.nn.softplus(
        jax.random.normal(keys[1], (rows, length, channels)))
    a = -jnp.broadcast_to(jnp.arange(1.0, states + 1), (channels, states))
    b = jax.random.normal(keys[2], (rows, length, states))
    cc = jax.random.normal(keys[3], (rows, length, states))
    d = jax.random.normal(keys[4], (channels,))
    dy = jax.random.normal(keys[5], (rows, length, channels))
    return (c, delta, a, b, cc, d), dy


def token_by_token(c, delta, a, b, cc, d):
    return ss.recurrence(c, delta, a, b, cc)[0] + d * c


PATHS = {"kernels": dict(use_pallas=True, interpret=True),
         "chunked": dict(use_pallas=False)}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("rate", ["fast", "slow", "mixed"])
def test_forward_and_every_gradient_against_the_recurrence(path, rate):
    """Four chunks of 8 positions a row: the state crosses three chunk
    boundaries forward and ``dh`` crosses them backward."""
    args, dy = inputs(1, 32, rate)
    want, want_vjp = jax.vjp(token_by_token, *args)
    got, got_vjp = jax.vjp(
        lambda *x: selective_scan(*x, chunk=8, **PATHS[path]), *args)
    np.testing.assert_allclose(got, want, atol=5e-6 * float(
        jnp.max(jnp.abs(want))))
    for name, g, w in zip(NAMES, got_vjp(dy), want_vjp(dy)):
        gap = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert gap < 5e-6, (name, gap)


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_state_crosses_the_chunk_boundary(path):
    """A slow channel's output at the first position of the second chunk
    holds what the first chunk wrote: with the first chunk's input
    noughted it changes, and it equals the one-chunk run's."""
    args, _ = inputs(2, 16, "slow")
    whole = selective_scan(*args, chunk=16, **PATHS[path])
    halves = selective_scan(*args, chunk=8, **PATHS[path])
    np.testing.assert_allclose(halves, whole, atol=5e-6 * float(
        jnp.max(jnp.abs(whole))))
    c = args[0].at[:, :8].set(0.0)
    cut = selective_scan(c, *args[1:], chunk=8, **PATHS[path])
    assert float(jnp.max(jnp.abs(cut[:, 8] - halves[:, 8]))) > 1e-3


def test_two_channel_blocks_and_two_rows_are_their_own():
    """2,048 channels are two grid blocks: each block's dB and dC parts
    are summed, each row's dA too."""
    args, dy = inputs(3, 16, "mixed", channels=2048, states=2)
    want, want_vjp = jax.vjp(token_by_token, *args)
    got, got_vjp = jax.vjp(
        lambda *x: selective_scan(*x, chunk=8, use_pallas=True,
                                  interpret=True), *args)
    np.testing.assert_allclose(got, want, atol=5e-6 * float(
        jnp.max(jnp.abs(want))))
    for name, g, w in zip(NAMES, got_vjp(dy), want_vjp(dy)):
        gap = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert gap < 5e-6, (name, gap)


def test_the_output_is_in_the_inputs_type_and_the_state_is_float32():
    """bfloat16 ``c`` in, bfloat16 ``y`` out; the state is float32 in
    between: a slow channel's sum over 64 positions keeps what bfloat16
    would round away."""
    args, _ = inputs(4, 64, "slow")
    c16 = args[0].astype(jnp.bfloat16)
    got = selective_scan(c16, *args[1:], chunk=16, use_pallas=True,
                         interpret=True)
    assert got.dtype == jnp.bfloat16
    want = token_by_token(c16.astype(jnp.float32), *args[1:])
    # bfloat16's rounding of the result alone: 2^-9 of it
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=2 ** -8,
                               atol=2 ** -8 * float(jnp.max(jnp.abs(want))))


def test_a_row_that_is_not_whole_chunks_is_refused():
    args, _ = inputs(5, 24, "fast")
    with pytest.raises(ValueError, match="whole number of chunks"):
        selective_scan(*args, chunk=16, use_pallas=False)
    with pytest.raises(ValueError, match="whole number of chunks"):
        selective_scan(*args, chunk=16, use_pallas=True, interpret=True)


def test_the_kernels_refuse_a_part_block_and_the_default_falls_back():
    """512 channels are half a block: asked for, the kernels refuse; not
    asked, the call runs as the ``jnp`` scans, and the counter says so
    (off a TPU always)."""
    args, _ = inputs(6, 16, "fast", channels=512)
    with pytest.raises(ValueError, match="blocks of 1024 channels"):
        selective_scan(*args, chunk=8, use_pallas=True, interpret=True)
    got = selective_scan(*args, chunk=8)
    np.testing.assert_allclose(got, token_by_token(*args), atol=5e-6)
    assert ss.fallback_passes(512) == 1 and ss.fallback_passes(1024) == 1
    assert not ss.kernels_by_default(1024)


def test_mismatched_operands_are_refused():
    (c, delta, a, b, cc, d), _ = inputs(7, 16, "fast")
    with pytest.raises(ValueError, match="delta"):
        selective_scan(c, delta[:, :8], a, b, cc, d, chunk=8)
    with pytest.raises(ValueError, match="B "):
        selective_scan(c, delta, a, b[..., :2], cc, d, chunk=8)
