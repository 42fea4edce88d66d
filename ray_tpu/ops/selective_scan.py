"""The selective scan of a Mamba-1 state-space layer, as two Pallas
kernels that keep the state in VMEM.

Per row a float32 state ``h [E, N]`` (``E`` channels, ``N`` states a
channel) starts at nought and at every position ``t``

    h_t = exp(delta_t (x) 1 . A) . h_{t-1} + (delta_t . c_t) (x) B_t
    y_t = h_t C_t + D . c_t

with ``delta_t``, ``c_t`` in R^E, ``B_t``, ``C_t`` in R^N and ``A`` in
R^{E x N} (negative).  The decay is per channel AND state, so a chunk of
positions has no matrix form (``ops/gated_delta.py`` has one scalar a
head and position): the positions are walked one by one, on the vector
unit, and what makes that cheap is that nothing of ``[positions, E, N]``
ever leaves VMEM.

**What sits where.**  ``softplus`` (and ``dt_proj``'s bias) are the
caller's: ``delta`` arrives positive.  The ``D`` skip is outside the
kernels, in this module's wrapper (``y = scan + D . c``, differentiated
by JAX); the gate ``silu(z)`` is the caller's too (a reader of the
ungated ``y`` exists: ``models/mamba.py``).  The kernels take and give
float32 (``c``, ``delta``, ``y`` are cast here: a position's 1,024
channels are then one ``[8, 128]`` register tile).

**Forward** (``selective_scan_fwd``): a grid over (row, block of 1,024
channels, chunk of ``chunk`` positions in order); a channel block's
state is ``N`` register tiles carried by the loop over the chunk's
positions, ``B_t`` and ``C_t`` are scalars read from SMEM, and the state
passes from chunk to chunk in a VMEM scratch.  It reads ``c``, ``delta``,
``B``, ``C`` and ``A`` and writes ``y`` and, for the backward, the state
ENTERING each chunk (float32, ``[rows, chunks, N, E]``: 84 MB at 16,384
positions in chunks of 64 and 5,120 x 16 states, against 5.4 GB for the
states of every position).

**Backward** (``selective_scan_bwd``): a grid over (row, chunk in
reverse order, channel block).  A step runs its chunk forward again from
the entering state, keeping the chunk's states in VMEM, then walks it
backwards carrying ``dh``: ``dc``, ``ddelta`` leave as they are made;
``dB`` and ``dC`` are sums over channels, accumulated unreduced over a
chunk's channel blocks in VMEM and written once a chunk with the
sublanes summed (``[rows, positions, N, 128]`` float32, the lanes summed
outside); ``dA`` accumulates over the chunks in VMEM.

Off the TPU, and where the shapes do not fit the kernels (``E`` not a
multiple of 1,024), the same recurrence runs as a ``lax.scan`` over
chunks whose body, a ``lax.scan`` over the chunk's positions, is under
``jax.checkpoint``: the backward again keeps the entering states alone.
``fallback_passes`` says when that path was taken.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Positions a grid step walks.  The backward holds three float32
#: ``[chunk, N, 8, 128]`` buffers in VMEM (12 MB at 64 and N = 16).
CHUNK = 64
#: Channels a grid step carries: one float32 register tile a state.
_LANES, _SUBLANES = 128, 8
BLOCK = _LANES * _SUBLANES
_VMEM_BYTES = 64 * 2 ** 20


def _advance(t, h, b_ref, x_ref, dt_ref, a_ref, states: int):
    """The states after position ``t`` of the chunk, from those before
    it: a tuple of ``states`` register tiles."""
    dt = dt_ref[t]
    x = dt * x_ref[t]
    return tuple(jnp.exp(dt * a_ref[n]) * h[n] + x * b_ref[t * states + n]
                 for n in range(states))


def _fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, y_ref, hin_ref, h_scr, *,
                chunk: int, states: int):
    # Grid (row, channel block, chunk).  b_ref / c_ref: SMEM [chunk * N],
    # position-major; x_ref / dt_ref / y_ref: [chunk, 8, 128]; a_ref /
    # hin_ref / h_scr: [N, 8, 128].
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hin_ref[...] = h_scr[...]

    def body(t, h):
        h = _advance(t, h, b_ref, x_ref, dt_ref, a_ref, states)
        y_ref[t] = sum(h[n] * c_ref[t * states + n] for n in range(states))
        return h

    h = jax.lax.fori_loop(0, chunk, body,
                          tuple(h_scr[n] for n in range(states)))
    for n in range(states):
        h_scr[n] = h[n]


def _bwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, hin_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                hs_scr, rb_scr, rc_scr, dh_scr, g_scr, *,
                chunk: int, states: int):
    # Grid (row, chunk from the last, channel block).  As the forward's
    # refs, and: dy_ref / dx_ref / ddt_ref [chunk, 8, 128]; db_ref /
    # dc_ref [chunk, N, 128] (the row's chunk, written at the last
    # channel block); da_ref [N, 8, 128] (the row's channel block,
    # every visit writes the sum so far); hs_scr [chunk + 1, N, 8, 128]:
    # the states from the one entering the chunk; rb_scr / rc_scr
    # [chunk, N, 8, 128]: dB and dC before any sum inside a tile; dh_scr
    # / g_scr [channel blocks, N, 8, 128]: dh leaving the chunk
    # backwards, and dA so far.
    step, blk = pl.program_id(1), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        dh_scr[blk] = jnp.zeros(dh_scr.shape[1:], jnp.float32)
        g_scr[blk] = jnp.zeros(g_scr.shape[1:], jnp.float32)

    @pl.when(blk == 0)
    def _():
        rb_scr[...] = jnp.zeros_like(rb_scr)
        rc_scr[...] = jnp.zeros_like(rc_scr)

    # the chunk forward again, every state kept
    hs_scr[0] = hin_ref[...]

    def again(t, h):
        h = _advance(t, h, b_ref, x_ref, dt_ref, a_ref, states)
        for n in range(states):
            hs_scr[t + 1, n] = h[n]
        return h

    jax.lax.fori_loop(0, chunk, again,
                      tuple(hin_ref[n] for n in range(states)))

    def back(i, dh):
        t = chunk - 1 - i
        dt = dt_ref[t]
        c = x_ref[t]
        x = dt * c
        dy = dy_ref[t]
        dx = jnp.zeros_like(dt)
        ddt = jnp.zeros_like(dt)
        new = []
        for n in range(states):
            a = a_ref[n]
            decay = jnp.exp(dt * a)
            dhn = dh[n] + dy * c_ref[t * states + n]
            rc_scr[t, n] += dy * hs_scr[t + 1, n]
            rb_scr[t, n] += dhn * x
            dx = dx + dhn * b_ref[t * states + n]
            g = dhn * hs_scr[t, n] * decay
            ddt = ddt + g * a
            g_scr[blk, n] += g * dt
            new.append(dhn * decay)
        dx_ref[t] = dx * dt
        ddt_ref[t] = ddt + dx * c
        return tuple(new)

    dh = jax.lax.fori_loop(0, chunk, back,
                           tuple(dh_scr[blk, n] for n in range(states)))
    for n in range(states):
        dh_scr[blk, n] = dh[n]
    da_ref[...] = g_scr[blk]

    @pl.when(blk == pl.num_programs(2) - 1)
    def _():
        db_ref[...] = jnp.sum(rb_scr[...], axis=2)
        dc_ref[...] = jnp.sum(rc_scr[...], axis=2)


def _tiles(x):
    """[rows, L, E] -> [rows, L, E // 128, 128] float32."""
    rows, length, e = x.shape
    return x.astype(jnp.float32).reshape(rows, length, e // _LANES, _LANES)


def _a_tiles(a):
    """A [E, N] -> [N, E // 128, 128] float32."""
    e, n = a.shape
    return a.astype(jnp.float32).T.reshape(n, e // _LANES, _LANES)


def _kernel_forward(c, delta, a, b, cc, chunk, interpret):
    """c, delta [rows, L, E]; a [E, N]; b, cc [rows, L, N] -> (y [rows,
    L, E] float32, the state entering each chunk [rows, L // chunk, N,
    E // 128, 128] float32)."""
    rows, length, e = c.shape
    n = a.shape[1]
    chunks, blocks = length // chunk, e // BLOCK
    per_t = pl.BlockSpec((None, chunk, _SUBLANES, _LANES),
                         lambda r, j, i: (r, i, j, 0))
    scalars = pl.BlockSpec((None, chunk * n), lambda r, j, i: (r, i),
                           memory_space=pltpu.SMEM)
    y, hin = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, states=n),
        grid=(rows, blocks, chunks),
        in_specs=[scalars, scalars, per_t, per_t,
                  pl.BlockSpec((n, _SUBLANES, _LANES),
                               lambda r, j, i: (0, j, 0))],
        out_specs=[per_t,
                   pl.BlockSpec((None, None, n, _SUBLANES, _LANES),
                                lambda r, j, i: (r, i, 0, j, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((rows, length, e // _LANES, _LANES),
                                 jnp.float32),
            jax.ShapeDtypeStruct((rows, chunks, n, e // _LANES, _LANES),
                                 jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, _SUBLANES, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan_fwd",
    )(b.astype(jnp.float32).reshape(rows, length * n),
      cc.astype(jnp.float32).reshape(rows, length * n),
      _tiles(c), _tiles(delta), _a_tiles(a))
    return y.reshape(rows, length, e), hin


@jax.named_scope("selective_scan_bwd")
def _kernel_backward(c, delta, a, b, cc, hin, dy, chunk, interpret):
    """-> (dc, ddelta [rows, L, E] float32, dA [E, N], dB, dC [rows, L,
    N] float32)."""
    rows, length, e = c.shape
    n = a.shape[1]
    chunks, blocks = length // chunk, e // BLOCK
    f32 = jnp.float32
    per_t = pl.BlockSpec((None, chunk, _SUBLANES, _LANES),
                         lambda r, i, j: (r, chunks - 1 - i, j, 0))
    scalars = pl.BlockSpec((None, chunk * n),
                           lambda r, i, j: (r, chunks - 1 - i),
                           memory_space=pltpu.SMEM)
    a_spec = pl.BlockSpec((n, _SUBLANES, _LANES), lambda r, i, j: (0, j, 0))
    summed = pl.BlockSpec((None, chunk, n, _LANES),
                          lambda r, i, j: (r, chunks - 1 - i, 0, 0))
    tiled = jax.ShapeDtypeStruct((rows, length, e // _LANES, _LANES), f32)
    state = (n, _SUBLANES, _LANES)
    dx, ddt, db, dc, da = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, states=n),
        grid=(rows, chunks, blocks),
        in_specs=[scalars, scalars, per_t, per_t, a_spec,
                  pl.BlockSpec((None, None, n, _SUBLANES, _LANES),
                               lambda r, i, j: (r, chunks - 1 - i, 0, j, 0)),
                  per_t],
        out_specs=[per_t, per_t, summed, summed,
                   pl.BlockSpec((None, n, _SUBLANES, _LANES),
                                lambda r, i, j: (r, 0, j, 0))],
        out_shape=[tiled, tiled,
                   jax.ShapeDtypeStruct((rows, length, n, _LANES), f32),
                   jax.ShapeDtypeStruct((rows, length, n, _LANES), f32),
                   jax.ShapeDtypeStruct((rows, n, e // _LANES, _LANES), f32)],
        scratch_shapes=[pltpu.VMEM((chunk + 1, *state), f32),
                        pltpu.VMEM((chunk, *state), f32),
                        pltpu.VMEM((chunk, *state), f32),
                        pltpu.VMEM((blocks, *state), f32),
                        pltpu.VMEM((blocks, *state), f32)],
        # dh and dA pass from chunk to chunk, dB and dC from channel
        # block to channel block: every axis runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="selective_scan_bwd",
    )(b.astype(f32).reshape(rows, length * n),
      cc.astype(f32).reshape(rows, length * n),
      _tiles(c), _tiles(delta), _a_tiles(a), hin, _tiles(dy))
    return (dx.reshape(rows, length, e), ddt.reshape(rows, length, e),
            jnp.sum(da, axis=0).reshape(n, e).T,
            jnp.sum(db, axis=-1), jnp.sum(dc, axis=-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernels(c, delta, a, b, cc, chunk, interpret):
    return _kernel_forward(c, delta, a, b, cc, chunk, interpret)[0]


def _scan_fwd(c, delta, a, b, cc, chunk, interpret):
    y, hin = _kernel_forward(c, delta, a, b, cc, chunk, interpret)
    return y, (c, delta, a, b, cc, hin)


def _scan_bwd(chunk, interpret, res, dy):
    c, delta, a, b, cc, hin = res
    dc, ddelta, da, db, dcc = _kernel_backward(c, delta, a, b, cc, hin, dy,
                                               chunk, interpret)
    return (dc.astype(c.dtype), ddelta.astype(delta.dtype),
            da.astype(a.dtype), db.astype(b.dtype), dcc.astype(cc.dtype))


_scan_kernels.defvjp(_scan_fwd, _scan_bwd)


def recurrence(c, delta, a, b, cc, state=None):
    """The rule token by token, float32: c, delta [rows, L, E], a [E,
    N], b, cc [rows, L, N] -> (y [rows, L, E] float32 without the ``D``
    skip, the state after the last position [rows, E, N])."""
    f32 = jnp.float32
    rows, _, e = c.shape
    if state is None:
        state = jnp.zeros((rows, e, a.shape[1]), f32)
    a = a.astype(f32)

    def step(h, at_t):
        c_t, dt_t, b_t, c_out = at_t
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * c_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_out[:, None, :], axis=-1)

    by_position = [jnp.moveaxis(x.astype(f32), 1, 0)
                   for x in (c, delta, b, cc)]
    state, y = jax.lax.scan(step, state, tuple(by_position))
    return jnp.moveaxis(y, 0, 1), state


def _chunked(c, delta, a, b, cc, chunk):
    """``recurrence`` as a scan over chunks whose body is rematerialised:
    differentiated, it keeps the state entering each chunk."""
    rows, length, e = c.shape
    chunks = length // chunk

    def split(x):
        return jnp.moveaxis(x.reshape(rows, chunks, chunk, x.shape[-1]), 1, 0)

    @jax.checkpoint
    def one(h, at):
        y, h = recurrence(*at[:2], a, *at[2:], state=h)
        return h, y

    _, y = jax.lax.scan(one, jnp.zeros((rows, e, a.shape[1]), jnp.float32),
                        tuple(map(split, (c, delta, b, cc))))
    return jnp.moveaxis(y, 0, 1).reshape(rows, length, e)


def kernels_by_default(channels: int) -> bool:
    """Whether ``selective_scan`` runs as the two kernels where the call
    does not say: on a TPU, at a whole number of channel blocks."""
    return jax.default_backend() == "tpu" and channels % BLOCK == 0


def fallback_passes(channels: int) -> int:
    """The counter ``ssm_scan_fallback_passes`` of a layer: 1 where its
    scan runs as the ``jnp`` scans and not as the kernels."""
    return 0 if kernels_by_default(channels) else 1


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def selective_scan(c: jax.Array, delta: jax.Array, A: jax.Array,
                   B: jax.Array, C: jax.Array, D: jax.Array,
                   chunk: int = CHUNK, use_pallas: bool | None = None,
                   interpret: bool = False) -> jax.Array:
    """c [rows, L, E] (the convolution's output, after its SiLU), delta
    [rows, L, E] (after ``softplus``: positive), A [E, N] (negative), B,
    C [rows, L, N], D [E] -> y [rows, L, E] in ``c``'s dtype, the ``D``
    skip included and no gate applied.  L a whole number of ``chunk``s
    (a row that is not is refused: pad upstream).  ``use_pallas`` None:
    the kernels on a TPU where E is a whole number of 1,024-channel
    blocks, the ``jnp`` scans elsewhere; ``interpret`` runs the kernels
    in the Pallas interpreter (CPU tests)."""
    rows, length, e = c.shape
    chunk = min(chunk, length)
    if length % chunk:
        raise ValueError(f"a row of {length} positions is not a whole "
                         f"number of chunks of {chunk}; pad upstream")
    if delta.shape != c.shape or A.shape[0] != e or \
            B.shape != (rows, length, A.shape[1]) or C.shape != B.shape:
        raise ValueError(f"c {c.shape}, delta {delta.shape}, A {A.shape}, "
                         f"B {B.shape}, C {C.shape}")
    if use_pallas is None:
        use_pallas = kernels_by_default(e)
    if use_pallas:
        if e % BLOCK:
            raise ValueError(f"the kernels walk blocks of {BLOCK} channels; "
                             f"{e} is not a whole number of them")
        y = _scan_kernels(c, delta, A, B, C, chunk, interpret)
    else:
        y = _chunked(c, delta, A, B, C, chunk)
    skip = D.astype(jnp.float32) * c.astype(jnp.float32)
    return (y + skip).astype(c.dtype)
