"""A short causal depthwise convolution and the SiLU after it, as one
pass over the channels in each direction: two Pallas kernels behind a
``custom_vjp``.

For ``x [B, S, C]`` and ``taps [C, K]`` (``K`` a handful: 4 in the delta
layers),

    pre[t] = sum_j x[t - (K - 1 - j)] . taps[j]     zeros before t = 0
    y      = silu(pre)                               float32

The filter is depthwise -- some ``2 K`` operations an element, nothing
for the MXU -- so the memory's rate bounds it and what counts is that
every array crosses HBM once.  As XLA makes it (``causal_conv`` below,
which stays as the path off the TPU and as the kernels' oracle) a padded
float32 copy of ``x`` is written and read ``K`` times, and the backward
keeps or recomputes the float32 sum.

**The layout: positions along the lanes.**  The kernels take ``x`` as
``[B, C, S]``.  That is how XLA lays a projection's output out on the
TPU (``bf16[2,8192,16,768]{1,3,2,0}``: the positions minor), what the
layer's other passes read and write, and so the transposes around the
kernels are bitcasts; taking ``[B, S, C]`` instead cost a transposing
copy of every operand and result (compiled for a described v5e, PR 41).
A grid step holds a ``[256, block_s]`` tile (``[128, ..]`` where 256
does not divide the channels) and walks it 64 channels at a time, 128
positions after 128: the position ``d`` before a position is a lane
rotation of the 128 positions selected with the 128 before them, the
taps lie along the lanes, and the 128 positions before the tile arrive
as a second small block of the same array (zeros at a row's first
tile).  A 16-bit input is rotated as the packed 32-bit words it is,
before the cast.  Every grid axis is parallel.

**Groups.**  ``x [B, S, G, W]`` with ``taps [G, Cw, K]``, ``Cw <= W``,
convolves the first ``Cw`` columns of every group and reads them
THROUGH THE BLOCK INDEX of ``[B, G W, S]``: the delta layer's fused
projection is ``[q | k | v | z]`` a key head and only ``q | k | v`` go
through the filter, so no sliced copy of it is made in either
direction.  ``dx`` comes back at ``x``'s whole shape with zeros in the
columns the filter skipped.

**Forward** (``causal_conv_fwd``): reads the tile once in the input's
dtype, sums the taps in float32 in ``causal_conv``'s order (the oldest
position first), applies SiLU, writes float32 once.

**Backward** (``causal_conv_bwd``): the residuals are ``x`` and the taps
alone.  A step makes ``pre`` again from ``x``, ``dpre = dy . silu'(pre)``
into a VMEM scratch (with the 128 positions after the tile, from a block
of ``x`` and of ``dy`` each: the filter runs the other way), then
``dx[t] = sum_j dpre[t + (K - 1 - j)] . taps[j]`` from that scratch, and
the taps' gradient as the tile's own float32 sum ``[block, K]``; the
tiles' sums are added outside the kernel.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Positions a grid step holds at most: the backward's tiles and its
#: scratch are 21 MB of VMEM at 4,096.
BLOCK_S = 4096
_LANES = 128
#: Channels and groups of 128 positions an iteration of the walk holds:
#: 64 x 128 is eight float32 register tiles a value, and four groups
#: written out make 32 independent chains of rotations, exponentials and
#: quotients, enough to hide their latencies, in a body a quarter of a
#: whole tile's written out (what the step's trace and lowering pay
#: for).  On the chip at the hybrid cell's shape, forward / backward ms a
#: call: 1.29 / 3.33 here; 16 channels with a tile of 2,048 written out
#: 1.30 / 3.08 in 2.4 times the equations; 64 x 2 groups 1.66 / 4.08,
#: 32 x 4 1.68 / 4.17, 16 x 2 4.4 / 9.0 (PERF.md section 6, PR 41).
_GROUP = 64
_UNROLL = 4
_VMEM_BYTES = 32 * 2 ** 20


def causal_conv(x, taps):
    """x [B, S, ..., C], taps [..., C, K]: each channel over its own last
    K positions (tap K - 1 on the position itself), zeros before the
    row's start; summed in float32."""
    k = taps.shape[-1]
    seq = x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32),
                     [(0, 0), (k - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    taps = taps.astype(jnp.float32)
    return sum(padded[:, j:j + seq] * taps[..., j] for j in range(k))


def _words(x):
    """A group as the 32-bit words its lanes hold: 16 channels of a
    16-bit input are one packed register tile, a lane a position of two
    channels, so one rotation moves what would be two after the cast."""
    return pltpu.bitcast(x, jnp.uint32) if x.dtype.itemsize == 2 else x


def _masks(dtype, taps, ahead=False):
    """Entry ``d``: the lanes a group of ``dtype`` (as words) shifted by
    ``d`` positions takes from the group beside it: the last ``d`` of the
    one before, or (``ahead``) the first ``d`` of the one after."""
    rows = _GROUP * min(jnp.dtype(dtype).itemsize, 4) // 4
    lanes = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    return [lanes < d if ahead else lanes >= _LANES - d for d in range(taps)]


def _shifted(beside, cur, masks, ahead=False):
    """``cur`` [.., 128] as each tap sees it: entry ``j`` holds the
    position ``K - 1 - j`` before each of ``cur``'s (after, ``ahead``),
    ``beside`` being the 128 positions before (after) ``cur``."""
    return [pltpu.roll(jax.lax.select(masks[d], beside, cur),
                       _LANES - d if ahead else d, 1)
            for d in range(len(masks) - 1, 0, -1)] + [cur]


def _windows(prev, cur, masks, dtype):
    """``x[t - (K-1-j)]`` for every tap ``j`` in float32 [64, 128], from
    ``cur`` and the 128 positions before it as words."""
    wins = _shifted(prev, cur, masks)
    if dtype.itemsize == 2:
        wins = [pltpu.bitcast(win, dtype) for win in wins]
    return [win.astype(jnp.float32) for win in wins]


def _dot(wins, w):
    """Summed in ``causal_conv``'s order, the oldest position first."""
    return functools.reduce(jnp.add, (win * tap for win, tap in zip(wins, w)))


def _pre(wins, w):
    """The filter's sum before SiLU: ``_dot``, and the bias where ``w``
    holds one more column than there are windows."""
    pre = _dot(wins, w)
    return pre + w[len(wins)] if len(w) > len(wins) else pre


def _weights(w_ref, channels, taps):
    """The taps of ``channels`` (a slice of 64), each along the lanes."""
    return [jnp.broadcast_to(w_ref[channels, j:j + 1], (_GROUP, _LANES))
            for j in range(taps)]


def _unless(flag, x):
    return jnp.where(flag, jnp.zeros_like(x), x)


def _group(g):
    return pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)


def _along(block_s, body, carry):
    """``body(128 positions, carry) -> carry`` over a tile's positions in
    order, ``_UNROLL`` groups of 128 written out an iteration."""
    groups = block_s // _LANES
    unroll = min(_UNROLL, groups)

    def step(i, carry):
        for k in range(unroll):
            carry = body(pl.ds(pl.multiple_of(
                (i * unroll + k) * _LANES, _LANES), _LANES), carry)
        return carry

    return jax.lax.fori_loop(0, groups // unroll, step, carry)


def _fwd_kernel(x_ref, before_ref, w_ref, y_ref, *, taps: int,
                biased: bool):
    # Grid (row, channel block, tile).  x_ref [block, block_s] in the
    # input's dtype, positions along the lanes; before_ref [block, 128]:
    # the positions before the tile, any at a row's first tile; w_ref
    # [block, K] float32 (and the bias in a column K where ``biased``);
    # y_ref [block, block_s] float32.
    block, block_s = x_ref.shape
    first = pl.program_id(2) == 0
    masks = _masks(x_ref.dtype, taps)

    def walk(g, _):
        at = _group(g)
        w = _weights(w_ref, at, taps + biased)

        def forward(here, prev):
            cur = _words(x_ref[at, here])
            pre = _pre(_windows(prev, cur, masks, x_ref.dtype), w)
            y_ref[at, here] = pre * jax.lax.logistic(pre)
            return cur

        _along(block_s, forward, _words(_unless(first, before_ref[at, :])))
        return 0

    jax.lax.fori_loop(0, block // _GROUP, walk, 0)


def _bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
                dx_ref, dw_ref, dpre_scr, *, taps: int, biased: bool,
                per: int, stride: int):
    # Grid (row, channel block of x, tile).  As the forward's refs, and:
    # after_ref / dy_after_ref [block, 128]: the positions after the
    # tile, any at a row's last tile; dy_ref [block, block_s] float32;
    # dx_ref [block, block_s] in x's dtype; dw_ref [block, K] float32:
    # this tile's share of the taps' gradient (and of the bias', in a
    # column K where ``biased``); dpre_scr [block, block_s + 128]
    # float32.  A channel block the filter skips (``per`` of
    # every ``stride`` are its) gets zeros.
    tile = functools.partial(
        _bwd_tile, x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
        dx_ref, dw_ref, dpre_scr, taps, biased, pl.program_id(2) == 0,
        pl.program_id(2) == pl.num_programs(2) - 1)
    if per == stride:
        return tile()
    filtered = pl.program_id(1) % stride < per
    pl.when(filtered)(tile)

    @pl.when(jnp.logical_not(filtered))
    def _():
        dx_ref[...] = jnp.zeros_like(dx_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)


def _bwd_tile(x_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
              dx_ref, dw_ref, dpre_scr, taps, biased, first, last):
    f32 = jnp.float32
    block, block_s = x_ref.shape
    nought = jnp.zeros((_GROUP, _LANES), f32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, nought.shape, 1)
    behind = _masks(x_ref.dtype, taps)
    ahead = _masks(f32, taps, ahead=True)

    def walk(g, _):
        at = _group(g)
        w = _weights(w_ref, at, taps + biased)

        def dpre_of(prev, cur, dy):
            wins = _windows(prev, cur, behind, x_ref.dtype)
            pre = _pre(wins, w)
            s = jax.lax.logistic(pre)
            return dy * (s * (1.0 + pre * (1.0 - s))), wins

        def forward(here, carry):
            prev, *acc = carry
            cur = _words(x_ref[at, here])
            dpre, wins = dpre_of(prev, cur, dy_ref[at, here])
            dpre_scr[at, here] = dpre
            # (the bias' gradient: dpre against a window of ones)
            return (cur, *(a + dpre * win for a, win in zip(
                acc, wins + [1.0] * biased)))

        tail, *acc = _along(
            block_s, forward,
            (_words(_unless(first, before_ref[at, :])),)
            + (nought,) * (taps + biased))
        # the positions after the tile see its last ones: their dpre is
        # the next tile's, needed here for the filter run backwards
        after, _ = dpre_of(tail, _words(after_ref[at, :]),
                           dy_after_ref[at, :])
        dpre_scr[at, block_s:] = _unless(last, after)
        dw_ref[at, :] = functools.reduce(jnp.add, (
            jnp.where(lanes == j, jnp.sum(a, axis=1, keepdims=True), 0.0)
            for j, a in enumerate(acc)))[:, :taps + biased]

        def backward(here, cur):
            nxt = dpre_scr[at, pl.ds(here.start + _LANES, _LANES)]
            dx = _dot(_shifted(nxt, cur, ahead, ahead=True), w[:taps])
            dx_ref[at, here] = dx.astype(dx_ref.dtype)
            return nxt

        _along(block_s, backward, dpre_scr[at, :_LANES])
        return 0

    jax.lax.fori_loop(0, block // _GROUP, walk, 0)


def _block_of(width: int, pitch: int) -> int:
    """The channel block: 256 where it divides both the filtered columns
    of a group and the group, else 128."""
    return 2 * _LANES if math.gcd(width, pitch) % (2 * _LANES) == 0 \
        else _LANES


def _kernel_forward(x, w, width, pitch, block_s, interpret, biased):
    """x [B, G pitch, S], w [G width, K] float32 (``biased``: [G width,
    K + 1], the bias last) -> y [B, G width, S] float32: the first
    ``width`` channels of every ``pitch`` filtered."""
    b, _, s = x.shape
    cout, cols = w.shape
    block = _block_of(width, pitch)
    per, stride = width // block, pitch // block
    tiles, ratio = s // block_s, block_s // _LANES

    def chan(j):
        return (j // per) * stride + j % per

    return pl.pallas_call(
        functools.partial(_fwd_kernel, taps=cols - biased, biased=biased),
        grid=(b, cout // block, tiles),
        in_specs=[
            pl.BlockSpec((None, block, block_s),
                         lambda r, j, i: (r, chan(j), i)),
            pl.BlockSpec((None, block, _LANES),
                         lambda r, j, i: (r, chan(j),
                                          jnp.maximum(i * ratio - 1, 0))),
            pl.BlockSpec((block, cols), lambda r, j, i: (j, 0))],
        out_specs=pl.BlockSpec((None, block, block_s),
                               lambda r, j, i: (r, j, i)),
        out_shape=jax.ShapeDtypeStruct((b, cout, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="causal_conv_fwd",
    )(x, x, w)


def _kernel_backward(x, w, dy, width, pitch, block_s, interpret, biased):
    """-> (dx as ``x``, zeros in the channels the filter skips; dw [G
    width, K] float32)."""
    b, cin, s = x.shape
    cout, taps = w.shape
    block = _block_of(width, pitch)
    per, stride = width // block, pitch // block
    tiles, ratio = s // block_s, block_s // _LANES

    def at(j, i):
        """(channel block of x, of y, tile) a step reads: its own, or --
        at a block the filter skips -- what the step before it read, so
        nothing is fetched for it."""
        group, within = j // stride, j % stride
        skipped = within >= per
        within = jnp.minimum(within, per - 1)
        return (group * stride + within, group * per + within,
                jnp.where(skipped, tiles - 1, i))

    def index(which, beside=0):
        """The block of operand ``which`` (0: x, 1: dy) a step reads: its
        tile, or the 128 positions before (-1) or after (1) it, clamped
        at a row's ends (where the kernel reads noughts instead)."""
        def index_map(r, j, i):
            read = at(j, i)
            tile = read[2]
            if beside < 0:
                tile = jnp.maximum(tile * ratio - 1, 0)
            elif beside > 0:
                tile = jnp.minimum((tile + 1) * ratio, s // _LANES - 1)
            return r, read[which], tile
        return index_map

    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps - biased, biased=biased,
                          per=per, stride=stride),
        grid=(b, cin // block, tiles),
        in_specs=[
            pl.BlockSpec((None, block, block_s), index(0)),
            pl.BlockSpec((None, block, _LANES), index(0, -1)),
            pl.BlockSpec((None, block, _LANES), index(0, 1)),
            pl.BlockSpec((None, block, block_s), index(1)),
            pl.BlockSpec((None, block, _LANES), index(1, 1)),
            pl.BlockSpec((block, taps), lambda r, j, i: (at(j, i)[1], 0))],
        out_specs=[
            pl.BlockSpec((None, block, block_s), lambda r, j, i: (r, j, i)),
            pl.BlockSpec((None, None, block, taps),
                         lambda r, j, i: (r, i, j, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, tiles, cin, taps), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, block_s + _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="causal_conv_bwd",
    )(x, x, x, dy, dy, w)
    dw = jnp.sum(dw, axis=(0, 1)).reshape(cin // pitch, pitch, taps)
    return dx, dw[:, :width].reshape(cout, taps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _conv_kernels(x, w, width, pitch, block_s, interpret, biased):
    return _kernel_forward(x, w, width, pitch, block_s, interpret, biased)


def _conv_fwd(x, w, width, pitch, block_s, interpret, biased):
    return _kernel_forward(x, w, width, pitch, block_s, interpret,
                           biased), (x, w)


def _conv_bwd(width, pitch, block_s, interpret, biased, res, dy):
    return _kernel_backward(*res, dy, width, pitch, block_s, interpret,
                            biased)


_conv_kernels.defvjp(_conv_fwd, _conv_bwd)


def tile_of(seq: int) -> int:
    """The positions a grid step holds for a row of ``seq``: the largest
    of ``BLOCK_S``, its half, ... 128 that divides it; 0 where none."""
    block_s = BLOCK_S
    while block_s >= _LANES and seq % block_s:
        block_s //= 2
    return block_s if block_s >= _LANES else 0


def fits(x_shape, taps_shape, block_s: int | None = None) -> bool:
    """Whether the kernels take ``x [B, S, ..., W]`` and ``taps [...,
    Cw, K]`` at tiles of ``block_s`` positions (``tile_of`` the row where
    none is given): the filtered columns of a group and the group whole
    128-lane blocks, the row whole tiles of 128, 256 or a multiple of
    512 positions."""
    seq, pitch, width = x_shape[1], x_shape[-1], taps_shape[-2]
    block_s = tile_of(seq) if block_s is None else block_s
    groups = block_s // _LANES
    return (tuple(x_shape[2:-1]) == tuple(taps_shape[:-2]) and width <= pitch
            and math.gcd(width, pitch) % _LANES == 0 and block_s > 0
            and block_s % _LANES == 0 and seq % block_s == 0
            and groups % min(_UNROLL, groups) == 0)


def kernels_by_default(x_shape, taps_shape) -> bool:
    """Whether ``causal_conv_silu`` runs as the two kernels: on a TPU, at
    shapes that fit them."""
    return jax.default_backend() == "tpu" and fits(x_shape, taps_shape)


def fallback_passes(x_shape, taps_shape) -> int:
    """The counter ``gdn_conv_fallback_passes`` of a layer: 1 where its
    convolution runs as ``causal_conv`` and XLA's SiLU, not as the
    kernels."""
    return 0 if kernels_by_default(x_shape, taps_shape) else 1


def in_kernels(x, taps, block_s: int | None = None, interpret: bool = False,
               bias=None):
    """``causal_conv_silu`` by the kernels whatever the backend, a tile
    ``block_s`` positions: what ``causal_conv_silu`` calls on a TPU, and
    the tests in the Pallas interpreter.  ``bias [..., Cw]`` rides as
    one more column of the taps."""
    if not fits(x.shape, taps.shape, block_s):
        raise ValueError(f"x {x.shape}, taps {taps.shape}, tiles of "
                         f"{block_s} positions: not the kernels' shapes")
    width, pitch = taps.shape[-2], x.shape[-1]
    groups = math.prod(taps.shape[:-2])
    # positions along the lanes: as XLA lays the projection out
    x_t = jnp.swapaxes(x.reshape(*x.shape[:2], groups * pitch), 1, 2)
    w = taps.astype(jnp.float32).reshape(groups * width, -1)
    if bias is not None:
        w = jnp.concatenate(
            [w, bias.astype(jnp.float32).reshape(groups * width, 1)], axis=1)
    y = _conv_kernels(x_t, w, width, pitch, block_s or tile_of(x.shape[1]),
                      interpret, bias is not None)
    return jnp.swapaxes(y, 1, 2).reshape(*x.shape[:-1], width)


def causal_conv_silu(x: jax.Array, taps: jax.Array,
                     bias: jax.Array | None = None) -> jax.Array:
    """``silu(causal_conv(x[..., :Cw], taps) + bias)`` in float32 for x
    [B, S, ..., W], taps [..., Cw, K] and bias [..., Cw] (none by
    default), ``Cw <= W``: each of the first ``Cw`` channels of a group
    over its own last ``K`` positions, zeros before the row's start.
    The two kernels where ``kernels_by_default`` says so (``x`` is then
    never sliced in HBM; the bias is one more column of the taps),
    ``causal_conv`` and XLA's SiLU elsewhere."""
    if kernels_by_default(x.shape, taps.shape):
        return in_kernels(x, taps, bias=bias)
    pre = causal_conv(x[..., :taps.shape[-2]], taps)
    return jax.nn.silu(pre if bias is None else pre + bias)
