"""Mamba-2's state-space rule (SSD, arXiv:2405.21060), chunked, as two
Pallas kernels that hold a chunk's work in VMEM.

Per head a float32 state ``S [N, P]`` starts at nought at the row's
start; at every position ``t`` it decays by a scalar of the head and
takes the input along the position's ``B``, and ``C`` reads it:

    S_t = exp(dt_t a) S_{t-1} + B_t^T (dt_t x_t)       a < 0, dt_t > 0
    y_t = C_t S_t

``B`` and ``C`` belong to a GROUP of heads (``H / G`` heads read one).
The ``D`` skip is the caller's.  Token by token that is ``L`` dependent
steps a row; here it runs in chunks of ``C`` positions (128).  With
``G`` the running sum of ``dt a`` inside a chunk, ``X = dt x`` and ``S``
the state entering it:

    Y  = (C B^T . exp(G_i - G_j), lower with the diagonal) X
         + diag(exp(G)) C S
    S <- exp(G_C) S + (exp(G_C - G) B)^T X

These are the gated delta rule's output line and state line with ``X``
for its ``V'`` (``ops/gated_delta.py``): no correction, no inverse.  The
masks, the decays along a chunk and the turn of a row of decays into a
column are that module's own (``_tile_masks``, ``_to_col``,
``_to_row``).

On a TPU the rule runs inside ``ssd_fwd``: a grid over (row x group,
blocks of chunks in order, the group's heads), the heads' states in a
float32 VMEM scratch.  ``C B^T`` is the group's, made once a block of
chunks when its first head comes, kept in VMEM for the others.  It
reads ``X`` (heads first, [B H, L, P]), ``B``, ``C`` ([B G, L, N]) and
``G`` (a ``jnp.cumsum`` outside), and writes ``y`` and the state
entering each grid step.  The MXU gets ``X``, ``B``, ``C``, the masked
``C B^T`` and the state in the inputs' dtype, accumulating in float32;
the decays and the state are float32.  No exponent is ever positive.

The gradient is by hand (``jax.custom_vjp`` over the rule).
``ssd_bwd`` takes the forward's grid with the blocks the other way
round: a step walks a head's state forward through its chunks from the
one the forward wrote, by the forward's own line, then walks them
backwards carrying ``dS`` in float32 (a scratch a head) and makes
``dX``, ``dG`` a head and ``dB``, ``dC`` summed over the group's heads
in float32 blocks that stay in VMEM while the heads pass: the masked
``dC B^T``'s two products are made once a block, from the heads' sum.

Off the TPU, and under ``use_pallas=False``, the same lines are batched
``jnp`` differentiated by JAX around a ``lax.scan`` over chunks
(``_chunked_ssd``): the oracle the kernels are held to in interpret
mode.  A row that is no whole number of chunks is padded at its end with
positions that neither decay nor write (``dt`` 0); their outputs are
dropped.

``y`` and the step states carry the names ``RESIDUAL_NAMES``; a layer
rematerialised under ``models.transformer.remat_layer`` keeps both
(``models/remat.py``: ``BASE_NAMES``) and so runs the forward kernel
once a layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.gated_delta import (_NT, _TN, _chunks_a_step, _dot,
                                     _tile_masks, _to_col, _to_row)

CHUNK = 128
#: What the forward kernel writes for the backward pass, named where the
#: custom_vjp makes them its residuals: ``y`` [B H, L, P] in the inputs'
#: dtype and the state entering each grid step [B H, N / step, N, P]
#: float32.
RESIDUAL_NAMES = ("ssd_y", "ssd_step_states")
_VMEM_BYTES = 48 * 2 ** 20


def _chunked_ssd(x, g, b, c, chunk):
    """The rule as batched ``jnp`` around a scan over chunks.  x [B, L,
    H, P] (``dt x``), g [B, L, H] float32 (``dt a``), b, c [B, L, G, N]
    -> y [B, L, H, P] in ``x``'s dtype."""
    bsz, length, h, p = x.shape
    groups, n_state = b.shape[2:]
    f32, dt = jnp.float32, x.dtype
    n = length // chunk
    r = h // groups

    def chunked(a):                     # [B, L, K, ...] -> [B, K, n, C, ...]
        a = a.reshape(bsz, n, chunk, *a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    x, b, c = chunked(x), chunked(b), chunked(c)
    total = jnp.cumsum(chunked(g.astype(f32)), axis=-1)     # [B, H, n, C]
    gamma = jnp.exp(total)
    to_end = jnp.exp(total[..., -1:] - total)
    decay = gamma[..., -1]                                  # [B, H, n]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    among = jnp.exp(jnp.where(
        lower, total[..., :, None] - total[..., None, :], -jnp.inf))

    cb = jnp.einsum("bgnik,bgnjk->bgnij", c, b, preferred_element_type=f32)
    cb = jnp.repeat(cb, r, axis=1)                          # [B, H, n, C, C]
    bh, ch = (jnp.repeat(a, r, axis=1) for a in (b, c))     # [B, H, n, C, N]
    kd = (bh.astype(f32) * to_end[..., None]).astype(dt)

    def step(s, xs):
        kd, x, decay = xs
        new = s * decay[..., None, None] + jnp.einsum(
            "bhcn,bhcp->bhnp", kd, x, preferred_element_type=f32)
        return new, s

    zero = jnp.zeros((bsz, h, n_state, p), f32)
    _, states = jax.lax.scan(step, zero, (jnp.moveaxis(kd, 2, 0),
                                          jnp.moveaxis(x, 2, 0),
                                          jnp.moveaxis(decay, 2, 0)))
    states = jnp.moveaxis(states, 0, 2)                     # [B, H, n, N, P]
    y = jnp.einsum("bhnij,bhnjp->bhnip", (cb * among).astype(dt), x,
                   preferred_element_type=f32) \
        + jnp.einsum("bhnik,bhnkp->bhnip",
                     (ch.astype(f32) * gamma[..., None]).astype(dt),
                     states.astype(dt), preferred_element_type=f32)
    y = jnp.moveaxis(y.astype(dt), 1, 3)                    # [B, n, C, H, P]
    return y.reshape(bsz, length, h, p)


# --------------------------------------------------------------------------
# The kernels: a tile is one chunk of 128 rows, the MXU's width.

def _local(g_row, b, c, cb, m):
    """What a chunk makes of its own rows: g_row (``G``) [1, C] float32,
    b, c [C, N], cb = ``C B^T`` [C, C] float32, ``m`` from
    ``_tile_masks``."""
    f32, dt = jnp.float32, b.dtype
    g_col = _to_col(g_row, m.eye)
    among = jnp.exp(jnp.where(m.lower, g_col - g_row, -jnp.inf))
    gamma_col = jnp.exp(g_col)
    end = jnp.sum(jnp.where(m.ends, g_row, 0.0), axis=1, keepdims=True)
    to_end = jnp.exp(end - g_col)                           # [C, 1]
    return dict(
        g_col=g_col, among=among, gamma_col=gamma_col, to_end=to_end,
        # exp(G_C) along a row of the state, [1, P]
        decay=jnp.exp(jnp.sum(jnp.where(m.last_row[0], g_col, 0.0), axis=0,
                              keepdims=True)),
        p=(cb * among).astype(dt),
        qg=(c.astype(f32) * gamma_col).astype(dt),
        kd=(b.astype(f32) * to_end).astype(dt))


def _fwd_kernel(x_ref, b_ref, c_ref, g_ref, y_ref, steps_ref, state, cb_scr,
                *, step: int, chunk: int):
    # Grid (row x group, block of ``step`` chunks in order, head of the
    # group).  x_ref, y_ref: [step C, P]; b_ref, c_ref: [step C, N];
    # g_ref: [step, C] float32, a chunk's G a row; steps_ref: the state
    # entering this grid step [N, P] float32; state: [heads, N, P]
    # float32; cb_scr: [step, C, C] float32, the group's C B^T.
    dt = x_ref.dtype
    blk, head = pl.program_id(1), pl.program_id(2)

    @pl.when(blk == 0)
    def _():
        state[head] = jnp.zeros(state.shape[1:], state.dtype)

    @pl.when(head == 0)
    def _():
        for j in range(step):
            at = slice(j * chunk, (j + 1) * chunk)
            cb_scr[j] = _dot(c_ref[at], b_ref[at], _NT)

    m = _tile_masks(chunk, chunk, x_ref.shape[1])
    s = state[head]
    steps_ref[...] = s
    for j in range(step):
        at = slice(j * chunk, (j + 1) * chunk)
        x = x_ref[at]
        loc = _local(g_ref[pl.ds(j, 1), :], b_ref[at], c_ref[at], cb_scr[j],
                     m)
        y_ref[at] = (_dot(loc["p"], x)
                     + _dot(loc["qg"], s.astype(dt))).astype(dt)
        s = s * loc["decay"] + _dot(loc["kd"], x, _TN)
    state[head] = s


def _bwd_kernel(x_ref, b_ref, c_ref, g_ref, s_ref, dy_ref, dx_ref, db_ref,
                dc_ref, dg_ref, dstate, cb_scr, dcb_scr, walked, *,
                step: int, chunk: int):
    # The forward's grid with the blocks the other way round.  s_ref [N,
    # P]: the state entering this grid step as ``ssd_fwd`` wrote it;
    # dx_ref, dg_ref: a head's, as x_ref, g_ref; db_ref, dc_ref [step C,
    # N] float32: the group's, summed over its heads while they pass;
    # dstate [heads, N, P] float32: each head's cotangent of the state
    # LEAVING the chunk at hand; dcb_scr [step, C, C] float32: the
    # heads' sum of the cotangent of the masked C B^T; walked [step, N,
    # P] float32: the chunks' entering states, by the forward's walk.
    f32, dt = jnp.float32, x_ref.dtype
    turn, head = pl.program_id(1), pl.program_id(2)
    parts = [slice(j * chunk, (j + 1) * chunk) for j in range(step)]

    @pl.when(turn == 0)
    def _():
        dstate[head] = jnp.zeros(dstate.shape[1:], dstate.dtype)

    @pl.when(head == 0)
    def _():
        for j, at in enumerate(parts):
            cb_scr[j] = _dot(c_ref[at], b_ref[at], _NT)
        dcb_scr[...] = jnp.zeros_like(dcb_scr)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    m = _tile_masks(chunk, chunk, x_ref.shape[1])
    local = [_local(g_ref[pl.ds(j, 1), :], b_ref[at], c_ref[at], cb_scr[j], m)
             for j, at in enumerate(parts)]
    s = s_ref[...]
    for j, at in enumerate(parts):
        walked[j] = s
        if j < step - 1:
            s = s * local[j]["decay"] + _dot(local[j]["kd"], x_ref[at], _TN)
    ds = dstate[head]
    for j in reversed(range(step)):
        at, loc = parts[j], local[j]
        x, dy, b, c = x_ref[at], dy_ref[at], b_ref[at], c_ref[at]
        entering, low = walked[j], ds.astype(dt)
        dx = _dot(loc["p"], dy, _TN) + _dot(loc["kd"], low)     # [C, P]
        dkd = _dot(x, low, _NT)                                 # [C, N]
        ddecay = jnp.sum(jnp.sum(entering * ds, axis=0, keepdims=True),
                         axis=1, keepdims=True)                 # [1, 1]
        dqg = _dot(dy, entering.astype(dt), _NT)                # [C, N]
        ds = ds * loc["decay"] + _dot(loc["qg"], dy, _TN)
        dp = _dot(dy, x, _NT)                                   # [C, C]
        dcb_scr[j] = dcb_scr[j] + dp * loc["among"]
        db_ref[at] = db_ref[at] + dkd * loc["to_end"]
        dc_ref[at] = dc_ref[at] + dqg * loc["gamma_col"]
        # the decays: d(G_i - G_j) of the mask, exp(G), exp(G_C - G),
        # exp(G_C)
        apart = dp * cb_scr[j] * loc["among"]
        dto_end = jnp.sum(dkd * b.astype(f32), axis=1,
                          keepdims=True) * loc["to_end"]        # [C, 1]
        dg_col = (jnp.sum(apart, axis=1, keepdims=True)
                  + jnp.sum(dqg * c.astype(f32), axis=1, keepdims=True)
                  * loc["gamma_col"] - dto_end)
        at_end = (jnp.sum(dto_end, axis=0, keepdims=True)
                  + ddecay * loc["decay"][:, :1])               # [1, 1]
        dg_ref[pl.ds(j, 1), :] = (
            _to_row(dg_col, m.eye) - jnp.sum(apart, axis=0, keepdims=True)
            + jnp.where(m.last_lane[0], at_end, 0.0))
        dx_ref[at] = dx.astype(dx_ref.dtype)
    dstate[head] = ds

    @pl.when(head == pl.num_programs(2) - 1)
    def _():
        for j, at in enumerate(parts):
            dcb = dcb_scr[j].astype(dt)
            dc_ref[at] = dc_ref[at] + _dot(dcb, b_ref[at])
            db_ref[at] = db_ref[at] + _dot(dcb, c_ref[at], _TN)


def _specs(heads, step, chunk, p, n_state, last=None):
    """The blocks a grid step reads, in blocks of chunks in order (or
    the other way round, where ``last`` is the last block)."""
    def blk(i):
        return i if last is None else last - i

    headed = pl.BlockSpec((None, step * chunk, p),
                          lambda g, i, h: (g * heads + h, blk(i), 0))
    grouped = pl.BlockSpec((None, step * chunk, n_state),
                           lambda g, i, h: (g, blk(i), 0))
    decays = pl.BlockSpec((None, None, step, chunk),
                          lambda g, i, h: (g * heads + h, blk(i), 0, 0))
    states = pl.BlockSpec((None, None, n_state, p),
                          lambda g, i, h: (g * heads + h, blk(i), 0, 0))
    return headed, grouped, decays, states


def _params():
    # the state passes from a block of chunks to the next, and the
    # group's C B^T and dB, dC from a head to the next
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)


def _kernel_forward(x, g, b, c, chunk, interpret):
    """x [B H, L, P], g (``G``) [B H, n / step, step, C] float32, b, c
    [B G, L, N] -> (y [B H, L, P], the state entering each grid step [B
    H, n / step, N, P] float32)."""
    bh, length, p = x.shape
    bg, _, n_state = b.shape
    heads, n = bh // bg, length // chunk
    step = _chunks_a_step(n)
    headed, grouped, decays, states = _specs(heads, step, chunk, p, n_state)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, step=step, chunk=chunk),
        grid=(bg, n // step, heads),
        in_specs=[headed, grouped, grouped, decays],
        out_specs=[headed, states],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bh, n // step, n_state, p),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, n_state, p), jnp.float32),
                        pltpu.VMEM((step, chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_fwd",
    )(x, b, c, g)


def _kernel_backward(x, g, b, c, steps, dy, chunk, interpret):
    """The operands of ``_kernel_forward``, the states it wrote and
    ``y``'s cotangent -> those of x, g, b, c (b, c float32)."""
    bh, length, p = x.shape
    bg, _, n_state = b.shape
    heads, n = bh // bg, length // chunk
    step = _chunks_a_step(n)
    headed, grouped, decays, states = _specs(heads, step, chunk, p, n_state,
                                             last=n // step - 1)
    wide = jax.ShapeDtypeStruct(b.shape, jnp.float32)
    dx, db, dc, dg = pl.pallas_call(
        functools.partial(_bwd_kernel, step=step, chunk=chunk),
        grid=(bg, n // step, heads),
        in_specs=[headed, grouped, grouped, decays, states, headed],
        out_specs=[headed, grouped, grouped, decays],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), wide, wide,
                   jax.ShapeDtypeStruct(g.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, n_state, p), jnp.float32),
                        pltpu.VMEM((step, chunk, chunk), jnp.float32),
                        pltpu.VMEM((step, chunk, chunk), jnp.float32),
                        pltpu.VMEM((step, n_state, p), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_bwd",
    )(x, b, c, g, steps, dy)
    return dx, dg, db.astype(b.dtype), dc.astype(c.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd_kernels(x, g, b, c, chunk, interpret):
    # (the names in the primal too: ``models/remat.py`` reads the
    # forward's jaxpr alone)
    return _ssd_fwd(x, g, b, c, chunk, interpret)[0]


def _ssd_fwd(x, g, b, c, chunk, interpret):
    y, steps = _kernel_forward(x, g, b, c, chunk, interpret)
    y = checkpoint_name(y, RESIDUAL_NAMES[0])
    steps = checkpoint_name(steps, RESIDUAL_NAMES[1])
    return y, (x, g, b, c, steps)


def _ssd_bwd(chunk, interpret, res, dy):
    return _kernel_backward(*res, dy, chunk, interpret)


_ssd_kernels.defvjp(_ssd_fwd, _ssd_bwd)


def _fused_ssd(x, g, b, c, chunk, interpret):
    """The rule as the two kernels; what ``_chunked_ssd`` returns."""
    bsz, length, h, p = x.shape
    n = length // chunk
    step = _chunks_a_step(n)

    def heads_first(a):                 # [B, L, K, D] -> [B K, L, D]
        return jnp.moveaxis(a, 2, 1).reshape(-1, length, a.shape[-1])

    total = jnp.cumsum(jnp.moveaxis(g.astype(jnp.float32), 2, 1).reshape(
        bsz * h, n // step, step, chunk), axis=-1)
    y = _ssd_kernels(heads_first(x), total, heads_first(b), heads_first(c),
                     chunk, interpret)
    return jnp.moveaxis(y.reshape(bsz, h, length, p), 1, 2)


def kernels_by_default() -> bool:
    """Whether ``ssd_rule`` runs as the two kernels where the call does
    not say (``use_pallas=None``): on a TPU."""
    return jax.default_backend() == "tpu"


def fallback_passes() -> int:
    """The counter ``ssd_fallback_passes`` of a layer's rule: 1 where it
    runs as the chunked ``jnp`` form, 0 where as the kernels."""
    return 0 if kernels_by_default() else 1


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def ssd_rule(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int = CHUNK, use_pallas: bool | None = None,
             interpret: bool = False) -> jax.Array:
    """x [B, L, H, P], dt [B, L, H] float32 (the step sizes, > 0), a [H]
    float32 (the heads' rates, < 0), b, c [B, L, G, N] with ``G``
    dividing ``H`` (head ``h`` reads group ``h // (H / G)``) -> y [B, L,
    H, P] in ``x``'s dtype, without the ``D`` skip.  A row is one
    sequence: the state starts at nought and crosses whatever the row
    holds.  ``use_pallas`` None: the kernels on a TPU, the chunked
    ``jnp`` form elsewhere; ``interpret`` runs them in the Pallas
    interpreter (CPU tests)."""
    length, h = x.shape[1:3]
    if h % b.shape[2] or b.shape != c.shape:
        raise ValueError(f"{b.shape[2]} / {c.shape[2]} groups of B / C for "
                         f"{h} heads")
    dt32 = dt.astype(jnp.float32)
    xd = (x.astype(jnp.float32) * dt32[..., None]).astype(x.dtype)
    g = dt32 * a.astype(jnp.float32)
    pad = -length % chunk
    if pad:                             # positions that neither decay nor
        def padded(v):                  # write, dropped from y
            return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        xd, g, b, c = (padded(v) for v in (xd, g, b, c))
    if use_pallas is None:
        use_pallas = kernels_by_default()
    if use_pallas:
        y = _fused_ssd(xd, g, b, c, chunk, interpret)
    else:
        y = _chunked_ssd(xd, g, b, c, chunk)
    return y[:, :length]
