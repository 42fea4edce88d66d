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
masks and the turn of a row of decays into a column and back are that
module's own (``_tile_masks``, ``_to_col``, ``_to_row``).

**The layout: positions along the lanes.**  The layer's convolution
leaves ``x | B | C`` as one array laid out positions-minor (``[B, H P +
2 G N, L]`` in memory: ``ops/causal_conv.py``), the step sizes' projection
leaves ``dt`` so too (``[B, H, L]``), and the gated norm after the rule
reads ``y`` so.  The kernels take the convolution's output whole
(``ssd_mixed``) and read a grid step's head (``P`` channel rows) and its
group's ``B`` and ``C`` (``N`` rows each) through the block index, ``dt``
a head's row of positions, and ``a`` whole from SMEM.  Inside VMEM a
step makes ``X = dt x`` (rounded to the inputs' dtype) and ``G`` (a
float32 running sum along the lanes), and works on transposed tiles:
``X^T [P, C]``, ``B^T``, ``C^T [N, C]``, the state ``S^T [P, N]``, the
decays rows along the lanes.  It writes ``y^T`` into ``[B, H P, L]``.
So the transposes around the call are bitcasts, and nothing is sliced,
copied heads first, scaled by ``dt`` or summed along a chunk in HBM.

On a TPU the rule runs inside ``ssd_fwd``: a grid over (row x group,
blocks of chunks in order, the group's heads), the heads' states in a
float32 VMEM scratch.  ``C B^T`` is the group's, made once a block of
chunks when its first head comes, kept in VMEM for the others.  The MXU
gets ``X``, ``B``, ``C``, the masked ``C B^T`` and the state in the
inputs' dtype, accumulating in float32; the decays and the state are
float32.  No exponent is ever positive.  It writes ``y`` and the state
entering each grid step.

The gradient is by hand (``jax.custom_vjp`` over the rule).
``ssd_bwd`` takes the forward's grid with the blocks the other way
round: a step walks a head's state forward through its chunks from the
one the forward wrote, by the forward's own line, then walks them
backwards carrying ``dS`` in float32 (a scratch a head).  A head's
``dx = dX dt``, ``ddt`` (``sum_p dX x`` plus the reverse running sum of
``dG`` times ``a``) and that running sum (``da`` is its sum against
``dt``, an XLA reduction outside) are written as it passes; ``dB``,
``dC`` are summed over the group's heads in float32 VMEM blocks and
written in the inputs' dtype by the last (the masked ``dC B^T``'s two
products are made once a block, from the heads' sum).  ``dx`` ``[B, H
P, L]`` and ``dB``, ``dC`` ``[B, G N, L]`` are joined along the channels
into the cotangent of the convolution's output.

Off the TPU, and under ``use_pallas=False``, the same lines are batched
``jnp`` differentiated by JAX around a ``lax.scan`` over chunks
(``_chunked_ssd``): the oracle the kernels are held to in interpret
mode.  A row that is no whole number of chunks is padded at its end with
positions that neither decay nor write (``dt`` 0); their outputs are
dropped.

``y`` and the step states carry the names ``RESIDUAL_NAMES``; a layer
rematerialised under ``models.transformer.remat_layer`` keeps both
(``models/remat.py``: ``BASE_NAMES``) and so runs the forward kernel
once a layer.  The kernels' other residuals are their operands: the
convolution's output and ``dt``, which the layer keeps or makes again
(its names ``ssd_conv``, ``ssd_dt``).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.gated_delta import (_NT, _TN, _chunks_a_step, _dot,
                                     _tile_masks, _to_col, _to_row)

CHUNK = 128
#: What the forward kernel writes for the backward pass, named where the
#: custom_vjp makes them its residuals: ``y`` [B, H P, L] in the inputs'
#: dtype and the state entering each grid step, transposed, [B H, N /
#: step, P, N] float32.
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
# The kernels: a tile is one chunk of 128 positions along the lanes, the
# MXU's width.

def _running_sum(v, reverse=False):
    """Along the lanes of ``v [R, C]`` float32: at lane ``i`` the sum of
    lanes ``0..i`` (``reverse``: ``i..C-1``), in log2(C) rotations."""
    width = v.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    d = 1
    while d < width:
        if reverse:
            v = v + jnp.where(lane < width - d,
                              pltpu.roll(v, width - d, 1), 0.0)
        else:
            v = v + jnp.where(lane >= d, pltpu.roll(v, d, 1), 0.0)
        d *= 2
    return v


def _head(x_ref, dt_ref, a_ref, groups, chunk):
    """A grid step's head: (its rate ``a``, ``G`` [step, C] float32 a
    chunk a row, each chunk's ``X^T`` [P, C] in the inputs' dtype)."""
    a = a_ref[(pl.program_id(0) % groups) * pl.num_programs(2)
              + pl.program_id(2)]
    sizes = dt_ref[...]
    xs = [(x_ref[:, j * chunk:(j + 1) * chunk].astype(jnp.float32)
           * sizes[j:j + 1]).astype(x_ref.dtype)
          for j in range(sizes.shape[0])]
    return a, _running_sum(sizes * a), xs


def _local(g_row, b, c, cb, m):
    """What a chunk makes of its own positions: g_row (``G``) [1, C]
    float32, b, c (``B^T``, ``C^T``) [N, C], cb = ``C B^T`` [C, C]
    float32, ``m`` from ``_tile_masks``; the decays are rows [1, C],
    ``decay`` = exp(G_C) [1, 1]."""
    f32, dt = jnp.float32, b.dtype
    among = jnp.exp(jnp.where(m.lower, _to_col(g_row, m.eye) - g_row,
                              -jnp.inf))
    end = jnp.sum(jnp.where(m.last_lane[0], g_row, 0.0), axis=1,
                  keepdims=True)
    gamma, to_end = jnp.exp(g_row), jnp.exp(end - g_row)
    return dict(
        among=among, gamma=gamma, to_end=to_end, decay=jnp.exp(end),
        p=(cb * among).astype(dt),
        qg=(c.astype(f32) * gamma).astype(dt),
        kd=(b.astype(f32) * to_end).astype(dt))


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, steps_ref, state,
                cb_scr, *, chunk: int, groups: int):
    # Grid (row x group, block of ``step`` chunks in order, head of the
    # group).  x_ref, y_ref [P, step C]: the head's channels, positions
    # along the lanes; b_ref, c_ref [N, step C]: the group's; dt_ref
    # [step, C] float32: the head's step sizes, a chunk a row; a_ref [H]
    # float32 in SMEM; steps_ref: the state entering this grid step [P,
    # N] float32; state: [heads, P, N] float32; cb_scr: [step, C, C]
    # float32, the group's C B^T.
    dt = x_ref.dtype
    blk, head = pl.program_id(1), pl.program_id(2)
    parts = [slice(j * chunk, (j + 1) * chunk)
             for j in range(dt_ref.shape[0])]

    @pl.when(blk == 0)
    def _():
        state[head] = jnp.zeros(state.shape[1:], state.dtype)

    @pl.when(head == 0)
    def _():
        for j, at in enumerate(parts):
            cb_scr[j] = _dot(c_ref[:, at], b_ref[:, at], _TN)

    m = _tile_masks(chunk, chunk, 1)
    _, g, xs = _head(x_ref, dt_ref, a_ref, groups, chunk)
    s = state[head]
    steps_ref[...] = s
    for j, at in enumerate(parts):
        loc = _local(g[j:j + 1], b_ref[:, at], c_ref[:, at], cb_scr[j], m)
        y_ref[:, at] = (_dot(xs[j], loc["p"], _NT)
                        + _dot(s.astype(dt), loc["qg"])).astype(dt)
        s = s * loc["decay"] + _dot(xs[j], loc["kd"], _NT)
    state[head] = s


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, s_ref, dy_ref, dx_ref,
                db_ref, dc_ref, ddt_ref, dg_ref, dstate, cb_scr, dcb_scr,
                db_scr, dc_scr, walked, *, chunk: int, groups: int):
    # The forward's grid with the blocks the other way round.  s_ref [P,
    # N]: the state entering this grid step as ``ssd_fwd`` wrote it;
    # dy_ref, dx_ref: a head's, as x_ref; ddt_ref, dg_ref: a head's, as
    # dt_ref (dg_ref: the cotangent of ``dt a``); db_ref, dc_ref [N, step
    # C]: the group's, written by its last head from db_scr, dc_scr
    # float32, summed over its heads while they pass; dstate [heads, P,
    # N] float32: each head's cotangent of the state LEAVING the chunk at
    # hand; dcb_scr [step, C, C] float32: the heads' sum of the
    # cotangent of the masked C B^T; walked [step, P, N] float32: the
    # chunks' entering states, by the forward's walk.
    f32, dt = jnp.float32, x_ref.dtype
    turn, head = pl.program_id(1), pl.program_id(2)
    parts = [slice(j * chunk, (j + 1) * chunk)
             for j in range(dt_ref.shape[0])]

    @pl.when(turn == 0)
    def _():
        dstate[head] = jnp.zeros(dstate.shape[1:], dstate.dtype)

    @pl.when(head == 0)
    def _():
        for j, at in enumerate(parts):
            cb_scr[j] = _dot(c_ref[:, at], b_ref[:, at], _TN)
        dcb_scr[...] = jnp.zeros_like(dcb_scr)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    m = _tile_masks(chunk, chunk, 1)
    a, g, xs = _head(x_ref, dt_ref, a_ref, groups, chunk)
    local = [_local(g[j:j + 1], b_ref[:, at], c_ref[:, at], cb_scr[j], m)
             for j, at in enumerate(parts)]
    s = s_ref[...]
    for j in range(len(parts)):
        walked[j] = s
        if j < len(parts) - 1:
            s = s * local[j]["decay"] + _dot(xs[j], local[j]["kd"], _NT)
    ds = dstate[head]
    for j in reversed(range(len(parts))):
        at, loc, x = parts[j], local[j], xs[j]
        dy, b, c = dy_ref[:, at], b_ref[:, at], c_ref[:, at]
        entering, low = walked[j], ds.astype(dt)
        dx = _dot(dy, loc["p"]) + _dot(low, loc["kd"])         # [P, C]
        dkd = _dot(low, x, _TN)                                 # [N, C]
        ddecay = jnp.sum(jnp.sum(entering * ds, axis=0, keepdims=True),
                         axis=1, keepdims=True)                 # [1, 1]
        dqg = _dot(entering.astype(dt), dy, _TN)                # [N, C]
        ds = ds * loc["decay"] + _dot(dy, loc["qg"], _NT)
        dp = _dot(dy, x, _TN)                                   # [C, C]
        dcb_scr[j] = dcb_scr[j] + dp * loc["among"]
        db_scr[:, at] = db_scr[:, at] + dkd * loc["to_end"]
        dc_scr[:, at] = dc_scr[:, at] + dqg * loc["gamma"]
        # the decays: d(G_i - G_j) of the mask, exp(G), exp(G_C - G),
        # exp(G_C)
        apart = dp * cb_scr[j] * loc["among"]
        dto_end = jnp.sum(dkd * b.astype(f32), axis=0,
                          keepdims=True) * loc["to_end"]        # [1, C]
        at_end = (jnp.sum(dto_end, axis=1, keepdims=True)
                  + ddecay * loc["decay"])                      # [1, 1]
        dg_ref[pl.ds(j, 1), :] = (
            _to_row(jnp.sum(apart, axis=1, keepdims=True), m.eye)
            - jnp.sum(apart, axis=0, keepdims=True)
            + jnp.sum(dqg * c.astype(f32), axis=0, keepdims=True)
            * loc["gamma"] - dto_end
            + jnp.where(m.last_lane[0], at_end, 0.0))
        # X = dt x: x's cotangent and the first part of dt's
        sizes = dt_ref[pl.ds(j, 1), :]
        dx_ref[:, at] = (dx * sizes).astype(dx_ref.dtype)
        ddt_ref[pl.ds(j, 1), :] = jnp.sum(
            dx * x_ref[:, at].astype(f32), axis=0, keepdims=True)
    dstate[head] = ds
    # G is the running sum of dt a: its cotangent summed the other way
    dg = _running_sum(dg_ref[...], reverse=True)
    dg_ref[...] = dg
    ddt_ref[...] = ddt_ref[...] + dg * a

    @pl.when(head == pl.num_programs(2) - 1)
    def _():
        for j, at in enumerate(parts):
            dcb = dcb_scr[j].astype(dt)
            dc_ref[:, at] = (dc_scr[:, at] + _dot(b_ref[:, at], dcb, _NT)
                             ).astype(dc_ref.dtype)
            db_ref[:, at] = (db_scr[:, at] + _dot(c_ref[:, at], dcb)
                             ).astype(db_ref.dtype)


def _specs(dims, step, chunk, last=None):
    """The blocks a grid step (row x group ``q``, block ``i``, head ``h``
    of the group) reads and writes, in blocks of chunks in order (or the
    other way round, where ``last`` is the last block).  ``dims``:
    (heads a group, P, G, N)."""
    heads, p, groups, n_state = dims
    span, first_b = step * chunk, groups * heads * p // n_state

    def blk(i):
        return i if last is None else last - i

    def rows(size, at):
        return pl.BlockSpec((None, size, span),
                            lambda q, i, h: (q // groups, at(q, h), blk(i)))

    def per_head(*block):
        return pl.BlockSpec((None, None) + block,
                            lambda q, i, h: (q * heads + h, blk(i), 0, 0))

    return types.SimpleNamespace(
        # a head's channels of x (and of y, dy, dx)
        head=rows(p, lambda q, h: (q % groups) * heads + h),
        b=rows(n_state, lambda q, h: first_b + q % groups),
        c=rows(n_state, lambda q, h: first_b + groups + q % groups),
        group=rows(n_state, lambda q, h: q % groups),           # dB, dC
        sizes=per_head(step, chunk),                            # dt, ddt
        states=per_head(p, n_state),
        rates=pl.BlockSpec(memory_space=pltpu.SMEM))


def _params():
    # the state passes from a block of chunks to the next, and the
    # group's C B^T and dB, dC from a head to the next
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_BYTES)


def _grid(mixed, dims, chunk):
    heads, _, groups, _ = dims
    n = mixed.shape[2] // chunk
    step = _chunks_a_step(n)
    return (mixed.shape[0] * groups, n // step, heads), step


def _kernel_forward(mixed, dt, a, dims, chunk, interpret):
    """mixed [B, H P + 2 G N, L], dt [B H, n / step, step, C] float32, a
    [H] float32 -> (y [B, H P, L] in mixed's dtype, the state entering
    each grid step [B H, n / step, P, N] float32)."""
    heads, p, groups, n_state = dims
    grid, step = _grid(mixed, dims, chunk)
    spec = _specs(dims, step, chunk)
    bsz, _, length = mixed.shape
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, groups=groups),
        grid=grid,
        in_specs=[spec.head, spec.b, spec.c, spec.sizes, spec.rates],
        out_specs=[spec.head, spec.states],
        out_shape=[jax.ShapeDtypeStruct((bsz, groups * heads * p, length),
                                        mixed.dtype),
                   jax.ShapeDtypeStruct((dt.shape[0], grid[1], p, n_state),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((heads, p, n_state), jnp.float32),
                        pltpu.VMEM((step, chunk, chunk), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_fwd",
    )(mixed, mixed, mixed, dt, a)


def _kernel_backward(mixed, dt, a, steps, dy, dims, chunk, interpret):
    """The operands of ``_kernel_forward``, the states it wrote and
    ``y``'s cotangent -> those of mixed, dt and a."""
    heads, p, groups, n_state = dims
    grid, step = _grid(mixed, dims, chunk)
    spec = _specs(dims, step, chunk, last=grid[1] - 1)
    bsz, _, length = mixed.shape
    group = jax.ShapeDtypeStruct((bsz, groups * n_state, length), mixed.dtype)
    sizes = jax.ShapeDtypeStruct(dt.shape, jnp.float32)
    dx, db, dc, ddt, dg = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, groups=groups),
        grid=grid,
        in_specs=[spec.head, spec.b, spec.c, spec.sizes, spec.rates,
                  spec.states, spec.head],
        out_specs=[spec.head, spec.group, spec.group, spec.sizes,
                   spec.sizes],
        out_shape=[jax.ShapeDtypeStruct(dy.shape, mixed.dtype), group,
                   group, sizes, sizes],
        scratch_shapes=[pltpu.VMEM((heads, p, n_state), jnp.float32),
                        pltpu.VMEM((step, chunk, chunk), jnp.float32),
                        pltpu.VMEM((step, chunk, chunk), jnp.float32),
                        pltpu.VMEM((n_state, step * chunk), jnp.float32),
                        pltpu.VMEM((n_state, step * chunk), jnp.float32),
                        pltpu.VMEM((step, p, n_state), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_bwd",
    )(mixed, mixed, mixed, dt, a, steps, dy)
    da = jnp.sum((dg * dt).reshape(bsz, a.shape[0], -1), axis=(0, 2))
    return jnp.concatenate([dx, db, dc], axis=1), ddt, da


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ssd_kernels(mixed, dt, a, dims, chunk, interpret):
    # (the names in the primal too: ``models/remat.py`` reads the
    # forward's jaxpr alone)
    return _ssd_fwd(mixed, dt, a, dims, chunk, interpret)[0]


def _ssd_fwd(mixed, dt, a, dims, chunk, interpret):
    y, steps = _kernel_forward(mixed, dt, a, dims, chunk, interpret)
    y = checkpoint_name(y, RESIDUAL_NAMES[0])
    steps = checkpoint_name(steps, RESIDUAL_NAMES[1])
    return y, (mixed, dt, a, steps)


def _ssd_bwd(dims, chunk, interpret, res, dy):
    return _kernel_backward(*res, dy, dims, chunk, interpret)


_ssd_kernels.defvjp(_ssd_fwd, _ssd_bwd)


def _fused_ssd(mixed, dt, a, dims, chunk, interpret):
    """The rule as the two kernels: mixed [B, H P + 2 G N, L], dt [B, H,
    L] float32 -> y [B, H, P, L]."""
    bsz, _, length = mixed.shape
    step = _chunks_a_step(length // chunk)
    y = _ssd_kernels(mixed, dt.reshape(bsz * a.shape[0],
                                       length // (step * chunk), step, chunk),
                     a, dims, chunk, interpret)
    # (a reshape, so that what a rematerialised layer keeps is the
    # kernel's own output, which nothing else in the layer's forward
    # reads: ``jax.checkpoint`` makes a residual that is also read there
    # a pass of its own, a rounding to its own type)
    return y.reshape(bsz, a.shape[0], -1, length)


def kernels_by_default() -> bool:
    """Whether ``ssd_rule`` runs as the two kernels where the call does
    not say (``use_pallas=None``): on a TPU."""
    return jax.default_backend() == "tpu"


def fallback_passes() -> int:
    """The counter ``ssd_fallback_passes`` of a layer's rule: 1 where it
    runs as the chunked ``jnp`` form, 0 where as the kernels."""
    return 0 if kernels_by_default() else 1


@functools.partial(jax.jit, static_argnames=("n_groups", "state_size",
                                             "chunk", "use_pallas",
                                             "interpret"))
def ssd_mixed(mixed: jax.Array, dt: jax.Array, a: jax.Array, n_groups: int,
              state_size: int, chunk: int = CHUNK,
              use_pallas: bool | None = None,
              interpret: bool = False) -> jax.Array:
    """The rule on ``x | B | C`` as a Mamba-2 layer's convolution leaves
    them, every array positions-minor: mixed [B, H P + 2 G N, L] (``G =
    n_groups`` groups of ``N = state_size``), dt [B, H, L] float32 (the
    step sizes, > 0), a [H] float32 (the heads' rates, < 0) -> y [B, H,
    P, L] in mixed's dtype, without the ``D`` skip; head ``h`` reads group
    ``h // (H / G)``.  A row is one sequence: the state starts at nought
    and crosses whatever the row holds.  ``use_pallas`` None: the kernels
    on a TPU, the chunked ``jnp`` form elsewhere; ``interpret`` runs them
    in the Pallas interpreter (CPU tests)."""
    bsz, width, length = mixed.shape
    h = a.shape[0]
    hp = width - 2 * n_groups * state_size
    if hp <= 0 or hp % h or h % n_groups or dt.shape != (bsz, h, length):
        raise ValueError(f"{width} channels for {h} heads and {n_groups} "
                         f"groups of {state_size} states, dt {dt.shape}")
    if use_pallas is None:
        use_pallas = kernels_by_default()
    if use_pallas and hp % state_size:
        raise ValueError(f"the kernels read B and C in blocks of "
                         f"{state_size} channels behind {hp} of x")
    dt32, a32 = dt.astype(jnp.float32), a.astype(jnp.float32)
    pad = -length % chunk
    if pad:                             # positions that neither decay nor
        mixed, dt32 = (jnp.pad(v, [(0, 0), (0, 0), (0, pad)])   # write,
                       for v in (mixed, dt32))                   # dropped
    if use_pallas:
        y = _fused_ssd(mixed, dt32, a32,
                       (h // n_groups, hp // h, n_groups, state_size), chunk,
                       interpret)
    else:
        def rows(v, *shape):            # [B, K, L] -> [B, L, ...]
            return jnp.swapaxes(v, 1, 2).reshape(bsz, -1, *shape)
        gn = n_groups * state_size
        x = rows(mixed[:, :hp], h, hp // h)
        b, c = (rows(mixed[:, at:at + gn], n_groups, state_size)
                for at in (hp, hp + gn))
        dt32 = jnp.swapaxes(dt32, 1, 2)
        xd = (x.astype(jnp.float32) * dt32[..., None]).astype(x.dtype)
        y = jnp.moveaxis(_chunked_ssd(xd, dt32 * a32, b, c, chunk), 1, 3)
    return y[..., :length]


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def ssd_rule(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, chunk: int = CHUNK, use_pallas: bool | None = None,
             interpret: bool = False) -> jax.Array:
    """x [B, L, H, P], dt [B, L, H] float32 (the step sizes, > 0), a [H]
    float32 (the heads' rates, < 0), b, c [B, L, G, N] with ``G``
    dividing ``H`` (head ``h`` reads group ``h // (H / G)``) -> y [B, L,
    H, P] in ``x``'s dtype, without the ``D`` skip: ``ssd_mixed`` on the
    three laid side by side and positions-minor, as a layer's
    convolution leaves them."""
    bsz, length, h, p = x.shape
    if h % b.shape[2] or b.shape != c.shape:
        raise ValueError(f"{b.shape[2]} / {c.shape[2]} groups of B / C for "
                         f"{h} heads")
    groups, n_state = b.shape[2:]
    mixed = jnp.concatenate(
        [v.astype(x.dtype).reshape(bsz, length, -1) for v in (x, b, c)],
        axis=-1)
    y = ssd_mixed(jnp.swapaxes(mixed, 1, 2), jnp.swapaxes(dt, 1, 2), a,
                  groups, n_state, chunk=chunk, use_pallas=use_pallas,
                  interpret=interpret)
    return jnp.moveaxis(y, 3, 1)
