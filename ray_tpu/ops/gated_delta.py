"""The gated delta rule (Gated DeltaNet's linear attention), chunked, as
two Pallas kernels that hold everything a chunk makes in VMEM.

Per head a float32 state ``S [Dk, Dv]`` starts at nought at the row's
start and at every position ``t`` decays, takes a rank-one correction
towards ``v_t`` along ``k_t`` and is read by ``q_t``:

    S <- exp(g_t) S                       g_t <= 0
    S <- S + k_t (beta_t (v_t - S^T k_t))^T
    o_t = S^T q_t

Token by token that is ``L`` dependent steps a row.  Here it runs in
chunks of ``C`` positions (64).  With ``G`` the running sum of ``g``
inside a chunk, ``gamma = exp(G)`` and ``S`` the state entering it:

    A   = diag(beta) (K K^T . exp(G_i - G_j)), strictly lower
    T   = (I + A)^-1                       float32; A is nilpotent
    W   = T diag(beta gamma) K             U = T diag(beta) V
    V'  = U - W S                          what the chunk writes
    O   = (Q K^T . exp(G_i - G_j), lower with the diagonal) V'
          + diag(gamma) Q S
    S  <- exp(G_C) S + (exp(G_C - G) K)^T V'

On a TPU all seven lines run inside ``gated_delta_fwd``: a grid over
(row x head) and blocks of chunks in order, ``S`` in a float32 VMEM
scratch.  A grid step first makes what its chunks make of their own rows
(``K K^T``, ``Q K^T``, the decay masks, ``T``, ``W``, ``U``: no state in
it, so the chunks' short chains of small products overlap), then walks
the state through them.  It reads q, k, v, ``G`` (a ``jnp.cumsum``
outside: [B, H, L] float32) and ``beta``, and writes ``o``, the state
entering each grid step and the one entering the row's last chunk: no
``[C, C]`` tensor, no ``W``, ``U``, ``Kd`` or ``V'`` and no state a
chunk reaches HBM.  q and k may come with fewer heads than v (``Hk``
dividing ``H``): value head ``h`` reads key head ``h // (H / Hk)``
through the block index, nothing is repeated.

The kernels work on tiles of two chunks side by side (128 rows, the
MXU's width), every ``[128, 128]`` operand block-diagonal by chunk, so a
product costs what one chunk's would and serves two.  The inverse is
float32 at full precision (``Precision.HIGHEST``: Mosaic's
``contract_precision<fp32>``, six MXU passes a product, which is what
the kernels' time is made of: 4.4 ms of the forward's 7.7), by halves
down to 16-row blocks as ``_inverse`` has it and never the 64-row
product form (its powers reach 1e18 where rows are alike): the 16-row
blocks of a tile ride one block-diagonal operand (``(I - D)(I + D^2)(I
+ D^4)(I + D^8)``), then ``T <- T - T A' T`` with ``A'`` the part of
``A`` between the two halves of each 32-row block, then of each chunk.
Of two block-diagonal factors only the right one goes to the MXU as a
whole tile; the left one goes folded, its blocks side by side as ``[16,
128]``, so 16 rows pass through the MXU for 128 (``_kernel_inverse``).
The MXU gets every other operand in the inputs' dtype with float32
accumulation; the state, the decays, ``A`` and ``T`` are float32.  No
exponent is ever positive and nothing is divided by a decay, so a head
that forgets within a position (``g`` of -30) and one that never does
run alike.

The gradient is by hand (``jax.custom_vjp`` over the whole rule).  The
one forward kernel also writes the state entering each of its grid steps
(float32, [B H, N / step, Dk, Dv], ``step`` = 8 chunks where the row's
chunk count allows: the one array between the two rules that is not an
operand).  ``gated_delta_bwd`` takes the forward's grid the other way
round.  A grid step first makes its tiles' chunk-local parts (``K K^T``,
``T``, ``W``, ``U`` ...: once a tile), then walks the state forward
through its chunks from the one it was handed, by the forward's own two
lines (``V' = U - W S``, ``S <- exp(G_C) S + Kd^T V'``: the same casts
in the same order, so the states are the forward's), holding the chunks'
entering states in a VMEM scratch ([step, Dk, Dv] float32, 512 KB); then
it walks the chunks backwards carrying ``dS`` in float32 and makes
``dq``, ``dk``, ``dv``, ``dG`` and ``dbeta`` (``dA = -T^T dT T^T`` at
full precision); ``dq`` and ``dk`` leave a value head at a time and are
summed over a key head's value heads outside, as the reverse running sum
that turns ``dG`` into ``dg`` is.

Off the TPU, and under ``use_pallas=False``, the same lines are batched
``jnp`` differentiated by JAX (the inverse by hand) around a
``lax.scan`` over chunks (``_chunked_rule``): the oracle the kernels are
held to in interpret mode.

``o`` and the step states carry the names ``RESIDUAL_NAMES`` where the
``custom_vjp`` makes them its residuals, and in the primal function too.
A layer rematerialised under ``models.transformer.remat_layer`` keeps
those two always, as it keeps the flash kernel's ``out`` and ``lse``
(``models/remat.py``: ``BASE_NAMES``), and so runs this forward kernel
once a layer, not again in the backward pass: at [2 x 32 heads, 8,192,
128] 134 MB of ``o`` and 67 MB of states a layer (a state a chunk would
be 537 MB; PERF.md section 6, PR 44).  q, k, v, ``G`` and beta carry no
name: they are made again from what the step's plan keeps around the
rule (``models/gdn.py``: the fused projections; the convolution's output
is named and priced, and not worth its float32 bytes).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
# Chunks a grid step: the step's fixed cost is paid once for all of
# them, their blocks are fetched together and their chunk-local chains
# overlap.
_CHUNKS_A_STEP = (8, 4, 2, 1)
# What the forward kernel writes for the backward pass, named where the
# custom_vjp makes them its residuals: ``o`` [B H, L, Dv] in the inputs'
# dtype (the layer after the rule reads it) and the state entering each
# grid step [B H, N / step, Dk, Dv] float32 (``gated_delta_bwd`` reads
# it).  A ``jax.checkpoint`` whose policy saves these names
# (``models.transformer.remat_layer``) does not run the forward kernel
# again in the backward pass.  q, k, v, ``G`` and beta carry no name.
RESIDUAL_NAMES = ("delta_out", "delta_step_states")
_MXU_ROWS = 128
_HIGHEST = jax.lax.Precision.HIGHEST
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def _inverse(a):
    """``(I + a)^-1`` for strictly lower ``a [..., n, n]`` float32.  Up
    to 16 rows by the product ``(I - a)(I + a^2)(I + a^4)...`` (``a`` is
    nilpotent, so it ends); above by halves, ``[[T11, 0], [-T22 a21 T11,
    T22]]``: powers of a 64-row block can reach 1e18 where its rows are
    alike, those of a 16-row one stay under 1e4."""
    n = a.shape[-1]
    if n <= 16:
        t = jnp.eye(n, dtype=a.dtype) - a
        power, reach = _mm(a, a), 2
        while reach < n:
            t = t + _mm(t, power)
            reach *= 2
            if reach < n:
                power = _mm(power, power)
        return t
    h = n // 2
    t11, t22 = _inverse(a[..., :h, :h]), _inverse(a[..., h:, h:])
    t21 = -_mm(_mm(t22, a[..., h:, :h]), t11)
    return jnp.concatenate([
        jnp.concatenate([t11, jnp.zeros_like(a[..., :h, h:])], axis=-1),
        jnp.concatenate([t21, t22], axis=-1)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1``, ``a`` strictly lower triangular, float32."""
    return _inverse(a)


def _inverse_fwd(a):
    t = _inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(_mm(tt, dt), tt),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _state_pass_scan(w, u, kd, c):
    """The pass over the state as a scan over chunks.  w, kd [BH, N, C,
    Dk], u [BH, N, C, Dv], c [BH, N] float32 -> (every chunk's entering
    state [BH, N, Dk, Dv] float32, V' [BH, N, C, Dv])."""
    f32 = jnp.float32

    def chunk(s, x):
        w, u, kd, c = x
        vp = u.astype(f32) - jnp.einsum(
            "bck,bkv->bcv", w, s.astype(w.dtype), preferred_element_type=f32)
        vp = vp.astype(u.dtype)
        new = s * c[:, None, None] + jnp.einsum(
            "bck,bcv->bkv", kd, vp, preferred_element_type=f32)
        return new, (s, vp)

    zero = jnp.zeros((w.shape[0], w.shape[-1], u.shape[-1]), f32)
    _, (states, vp) = jax.lax.scan(
        chunk, zero, tuple(jnp.moveaxis(x, 1, 0) for x in (w, u, kd, c)))
    return jnp.moveaxis(states, 0, 1), jnp.moveaxis(vp, 0, 1)


def _chunks(x, chunk):
    """[B, L, H, ...] -> [B, H, L // chunk, chunk, ...]."""
    b, length, h = x.shape[:3]
    x = x.reshape(b, length // chunk, chunk, h, *x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


def _chunked_rule(q, k, v, g, beta, chunk):
    """The rule as batched ``jnp`` around the scan -> (o [B, L, H, Dv],
    the state entering each row's last chunk [B, H, Dk, Dv] float32)."""
    b, length, h, dv = v.shape
    dk = q.shape[-1]
    f32, dt = jnp.float32, v.dtype
    n = length // chunk
    if q.shape[2] != h:        # a key head's q and k, once a value head
        q, k = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (q, k))
    q, k, v = (_chunks(x, chunk) for x in (q, k, v))       # [B, H, N, C, D]
    g, beta = (_chunks(x.astype(f32), chunk) for x in (g, beta))

    total = jnp.cumsum(g, axis=-1)                         # G [B, H, N, C]
    gamma = jnp.exp(total)
    to_end = jnp.exp(total[..., -1:] - total)
    c = gamma[..., -1]                                     # [B, H, N]
    apart = total[..., :, None] - total[..., None, :]      # G_i - G_j
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    among = jnp.exp(jnp.where(lower, apart, -jnp.inf))     # j <= i
    before = jnp.where(jnp.tril(lower, -1), among, 0.0)    # j < i

    kk = jnp.einsum("...ik,...jk->...ij", k, k, preferred_element_type=f32)
    t = unit_lower_inverse(beta[..., :, None] * before * kk)
    w = jnp.einsum("...ij,...jk->...ik",
                   (t * (beta * gamma)[..., None, :]).astype(dt), k,
                   preferred_element_type=f32).astype(dt)
    u = jnp.einsum("...ij,...jv->...iv",
                   (t * beta[..., None, :]).astype(dt), v,
                   preferred_element_type=f32).astype(dt)
    kd = (k * to_end[..., None]).astype(dt)

    states, vp = _state_pass_scan(
        *(x.reshape(b * h, *x.shape[2:]) for x in (w, u, kd, c)))
    states = states.reshape(b, h, n, dk, dv)
    vp = vp.reshape(b, h, n, chunk, dv)

    qk = jnp.einsum("...ik,...jk->...ij", q, k, preferred_element_type=f32)
    o = jnp.einsum("...ij,...jv->...iv", (qk * among).astype(dt), vp,
                   preferred_element_type=f32) \
        + jnp.einsum("...ik,...kv->...iv", (q * gamma[..., None]).astype(dt),
                     states.astype(dt), preferred_element_type=f32)
    o = jnp.moveaxis(o.astype(dt), 1, 3).reshape(b, length, h, dv)
    return o, states[:, :, -1]


# --------------------------------------------------------------------------
# The kernels.  They work on tiles of ``R = m C`` rows: ``m`` chunks side
# by side (two of 64 rows: the MXU's 128), every ``[R, R]`` operand
# block-diagonal by chunk, so a product costs what one chunk's would and
# serves ``m``.  A tile's vectors (G, beta and what is made of them) come
# as rows [1, R], along lanes, and are turned into columns [R, 1], along
# sublanes, through the diagonal of an [R, R] tile.

def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _dot32(a, b, dims=_NN):
    """A float32 product at full precision."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _to_col(row, eye):
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _stack(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _tile_masks(tile: int, chunk: int, dv: int):
    """What every tile of a grid step shares: iotas and the masks of the
    chunks' blocks."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    tall = jax.lax.broadcasted_iota(jnp.int32, (tile, dv), 0)
    lower, strict, ends = rows >= cols, rows > cols, cols == chunk - 1
    if tile > chunk:                    # chunk is a power of two
        shift = chunk.bit_length() - 1
        same = (rows >> shift) == (cols >> shift)
        lower, strict = same & lower, same & strict
        ends = same & ((cols & (chunk - 1)) == chunk - 1)
    last = [(i + 1) * chunk - 1 for i in range(tile // chunk)]
    return types.SimpleNamespace(
        rows=rows, cols=cols, chunk=chunk, eye=rows == cols, lower=lower,
        strict=strict,
        ends=ends,                      # j its chunk's last position
        last_lane=[lane == i for i in last],
        last_row=[tall == i for i in last])


def _kernel_inverse(a, rows, cols, chunk):
    """``_inverse`` of every ``chunk``-row block of a block-diagonal
    tile ``a [R, R]`` in VMEM: the blocks of 16 rows first, then the
    halves of 32 and of 64 rows, as ``_inverse`` has them.

    Block-diagonal matrices are closed under the product, so all of a
    tile's blocks ride one MXU operand; and a product of two of them
    needs only ONE as a whole ``[R, R]`` tile, the right one.  The left
    one goes in folded, its ``R / b`` blocks of ``b`` rows side by side
    as ``[b, R]`` (block ``i``'s rows in lanes ``i b ...``): ``b`` rows
    through the MXU for ``R``, and two left operands of one right one
    stacked.  ``T <- T - T A' T`` for the halves works the same way:
    ``A'`` (the part of ``a`` between the two halves of each block of
    ``2 b`` rows) maps lanes of the lower half's block to those of the
    upper half's, where the folded product lands and is unfolded."""
    tile = a.shape[0]
    base = min(chunk, 16)

    def within(block):                  # the blocks of ``block`` rows
        shift = block.bit_length() - 1
        return (rows >> shift) == (cols >> shift)

    def fold(x, block):                 # block-diagonal [R, R] -> [b, R]
        return sum(x[i:i + block] for i in range(0, tile, block))

    def unfold(x, mask):                # [b, R] -> [R, R] where ``mask``
        wide = _stack([x] * (tile // x.shape[0]))
        return wide if mask is None else jnp.where(mask, wide, 0.0)

    blocks = None if base >= tile else within(base)
    d = a if base >= chunk else jnp.where(blocks, a, 0.0)
    d_f = fold(d, base)
    folded = jax.lax.broadcasted_iota(jnp.int32, (base, tile), 1)
    t_f = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (base, tile), 0)
                    == (folded & (base - 1) if base < tile else folded),
                    1.0, 0.0) - d_f
    power_f, reach = _dot32(d_f, d), 2
    while reach < base:
        reach *= 2
        power = unfold(power_f, blocks)
        if reach < base:                # t (I + p) and p p: one right operand
            both = _dot32(jnp.concatenate([t_f, power_f], axis=0), power)
            t_f, power_f = t_f + both[:base], both[base:]
        else:
            t_f = t_f + _dot32(t_f, power)
    t = unfold(t_f, blocks)
    block = base
    while block < chunk:
        inner = within(block)
        outer = within(2 * block) if 2 * block < tile else None
        # ``a`` is nought outside its chunks' blocks as it comes
        between = jnp.where(inner, 0.0, a if 2 * block >= chunk
                            else jnp.where(outer, a, 0.0))
        t_f = t_f if block == base else fold(t, block)
        t = t - unfold(_dot32(_dot32(t_f, between), t),
                       ~inner if outer is None else outer & ~inner)
        block *= 2
    return t


def _tile_local(q, k, v, g_row, b_row, m):
    """What a tile's chunks make of their own rows alone.  q, k [R, Dk],
    v [R, Dv], g_row (``G``), b_row [1, R] float32, ``m`` from
    ``_tile_masks``."""
    f32, dt = jnp.float32, v.dtype
    g_col, b_col = _to_col(g_row, m.eye), _to_col(b_row, m.eye)
    among = jnp.exp(jnp.where(m.lower, g_col - g_row, -jnp.inf))
    before = jnp.where(m.strict, among, 0.0)
    kk = _dot(k, k, _NT)
    t = _kernel_inverse(b_col * before * kk, m.rows, m.cols, m.chunk)
    gamma_row, gamma_col = jnp.exp(g_row), jnp.exp(g_col)
    tw = (t * (b_row * gamma_row)).astype(dt)
    tu = (t * b_row).astype(dt)
    to_end = jnp.exp(jnp.sum(jnp.where(m.ends, g_row, 0.0), axis=1,
                             keepdims=True) - g_col)       # [R, 1]
    qk = _dot(q, k, _NT)
    return types.SimpleNamespace(
        b_row=b_row, b_col=b_col, gamma_row=gamma_row, gamma_col=gamma_col,
        among=among, before=before, kk=kk, t=t, tw=tw, tu=tu, qk=qk,
        to_end=to_end,
        # a chunk's exp(G_C) along a row of the state, [1, Dv] (Mosaic
        # broadcasts along sublanes or along lanes, not both)
        c=[jnp.exp(jnp.sum(jnp.where(at, g_col, 0.0), axis=0, keepdims=True))
           for at in m.last_row],
        w=_dot(tw, k).astype(dt), u=_dot(tu, v).astype(dt),
        kd=(k.astype(f32) * to_end).astype(dt),
        p=(qk * among).astype(dt),
        qg=(q.astype(f32) * gamma_col).astype(dt))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, steps_ref, last_ref,
                state, *, step: int, chunk: int, tile: int):
    # Grid (row x head, block of ``step`` chunks, in order).  q_ref,
    # k_ref: [step C, Dk]; v_ref, o_ref: [step C, Dv]; g_ref, b_ref:
    # [N C / R, R] float32, a row's G and beta a tile a row, resident
    # across the head's blocks; state: [Dk, Dv] float32.  steps_ref: the
    # state entering this grid step, last_ref: the one entering the
    # row's last chunk, [Dk, Dv] float32 each.
    f32, dt = jnp.float32, v_ref.dtype
    blk = pl.program_id(1)
    tiles, per = step * chunk // tile, tile // chunk

    @pl.when(blk == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    m = _tile_masks(tile, chunk, v_ref.shape[1])
    local = []
    for j in range(tiles):
        at, row = slice(j * tile, (j + 1) * tile), pl.ds(blk * tiles + j, 1)
        local.append(_tile_local(q_ref[at], k_ref[at], v_ref[at],
                                 g_ref[row, :], b_ref[row, :], m))
    s = state[...]
    steps_ref[...] = s
    for j, x in enumerate(local):
        written, read = [], []
        for i in range(per):
            if (j, i) == (tiles - 1, per - 1):
                @pl.when(blk == pl.num_programs(1) - 1)
                def _():
                    last_ref[...] = s
            at = slice(i * chunk, (i + 1) * chunk)
            # W S and (gamma Q) S: the state is the MXU's operand once
            both = _dot(jnp.concatenate([x.w[at], x.qg[at]], axis=0),
                        s.astype(dt))
            vp = (x.u[at].astype(f32) - both[:chunk]).astype(dt)
            s = s * x.c[i] + _dot(x.kd[at], vp, _TN)
            written.append(vp)
            read.append(both[chunk:])
        o_ref[j * tile:(j + 1) * tile] = (
            _dot(x.p, _stack(written)) + _stack(read)).astype(dt)
    state[...] = s


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, dstate, walked, *, step: int,
                chunk: int, tile: int):
    # The forward's grid with the blocks, and the chunks of a block, the
    # other way round; dstate [Dk, Dv] float32 is the cotangent of the
    # state LEAVING the chunk at hand.  s_ref [Dk, Dv]: the state
    # entering this grid step as ``gated_delta_fwd`` wrote it; walked
    # [step, Dk, Dv] float32: each of its chunks' entering states, made
    # here by the forward's own walk.  dg_ref, db_ref: the cotangents of
    # G and beta, laid out and resident as g_ref and b_ref are.
    f32, dt = jnp.float32, v_ref.dtype
    turn = pl.program_id(1)
    blk = pl.num_programs(1) - 1 - turn
    tiles, per = step * chunk // tile, tile // chunk
    parts = [slice(i * chunk, (i + 1) * chunk) for i in range(per)]

    @pl.when(turn == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    m = _tile_masks(tile, chunk, v_ref.shape[1])
    local = []
    for j in range(tiles):
        at, row = slice(j * tile, (j + 1) * tile), pl.ds(blk * tiles + j, 1)
        local.append(_tile_local(q_ref[at], k_ref[at], v_ref[at],
                                 g_ref[row, :], b_ref[row, :], m))
    # the forward's walk through the step's chunks: its two lines, its
    # casts, its order, so the states are the forward's
    s, wrote = s_ref[...], []
    for j, x in enumerate(local):
        written = []
        for i, at in enumerate(parts):
            walked[j * per + i] = s
            written.append((x.u[at].astype(f32)
                            - _dot(x.w[at], s.astype(dt))).astype(dt))
            if (j, i) != (tiles - 1, per - 1):
                s = s * x.c[i] + _dot(x.kd[at], written[-1], _TN)
        wrote.append(_stack(written))
    ds = dstate[...]
    for j in reversed(range(tiles)):
        here, row = slice(j * tile, (j + 1) * tile), pl.ds(blk * tiles + j, 1)
        q, k, v, do = (ref[here] for ref in (q_ref, k_ref, v_ref, do_ref))
        x, vp = local[j], wrote[j]
        entering = [walked[j * per + i] for i in range(per)]
        # the two lines that hold the state, transposed, a chunk at a
        # time, the last first
        from_o = _dot(x.p, do, _TN)                         # P^T dO
        dvp, dkd, dc = [None] * per, [None] * per, [None] * per
        for i in reversed(range(per)):
            at, low = parts[i], ds.astype(dt)
            dvp[i] = (from_o[at] + _dot(x.kd[at], low)).astype(dt)
            dkd[i] = _dot(vp[at], low, _NT)                 # [C, Dk]
            dc[i] = jnp.sum(jnp.sum(entering[i] * ds, axis=0, keepdims=True),
                            axis=1, keepdims=True)          # [1, 1]
            ds = ds * x.c[i] + _dot(
                jnp.concatenate([x.qg[at], -x.w[at]], axis=0),
                jnp.concatenate([do[at], dvp[i]], axis=0), _TN)
        # dO S^T and dV' S^T: the state is the MXU's operand once
        by_state = [_dot(jnp.concatenate([do[at], dvp[i]], axis=0),
                         entering[i].astype(dt), _NT)
                    for i, at in enumerate(parts)]
        dqg = _stack([x[:chunk] for x in by_state])         # [R, Dk]
        dw = _stack([-x[chunk:] for x in by_state]).astype(dt)
        dvp, dkd = _stack(dvp), _stack(dkd)
        # O's first product
        dp = _dot(do, vp, _NT)                              # [R, R]
        dqk = (dp * x.among).astype(dt)
        # W, U and the inverse
        dtu, dtw = _dot(dvp, v, _NT), _dot(dw, k, _NT)      # [R, R]
        by_w = jnp.sum(dtw * x.t, axis=0, keepdims=True)    # [1, R]
        da = -_dot32(x.t, _dot32(
            dtu * x.b_row + dtw * (x.b_row * x.gamma_row), x.t, _NT), _TN)
        da_kk = da * x.before * x.kk
        dkk = (da * x.b_col * x.before).astype(dt)
        # the decays: d(G_i - G_j) of both masks, gamma, exp(G_C - G), c
        apart = dp * x.qk * x.among + da_kk * x.b_col
        dto_end = jnp.sum(dkd * k.astype(f32), axis=1,
                          keepdims=True) * x.to_end         # [R, 1]
        dg_col = (jnp.sum(apart, axis=1, keepdims=True)
                  + jnp.sum(dqg * q.astype(f32), axis=1,
                            keepdims=True) * x.gamma_col - dto_end)
        at_end = jnp.sum(jnp.where(m.ends, dto_end, 0.0), axis=0,
                         keepdims=True)
        for i, lane in enumerate(m.last_lane):
            at_end = at_end + jnp.where(
                lane, jnp.broadcast_to(dc[i], lane.shape) * x.gamma_row, 0.0)
        dg_ref[row, :] = (
            _to_row(dg_col, m.eye) - jnp.sum(apart, axis=0, keepdims=True)
            + by_w * x.b_row * x.gamma_row + at_end)
        db_ref[row, :] = (
            _to_row(jnp.sum(da_kk, axis=1, keepdims=True), m.eye)
            + jnp.sum(dtu * x.t, axis=0, keepdims=True)
            + by_w * x.gamma_row)
        dq_ref[here] = (_dot(dqk, k) + dqg * x.gamma_col).astype(dt)
        dk_ref[here] = (_dot(jnp.concatenate([x.tw, dqk, dkk], axis=0),
                           jnp.concatenate([dw, q, k], axis=0), _TN)
                      + _dot(dkk, k) + dkd * x.to_end).astype(dt)
        dv_ref[here] = _dot(x.tu, dvp, _TN).astype(dt)
    dstate[...] = ds


def _chunks_a_step(n: int) -> int:
    return next(s for s in _CHUNKS_A_STEP if n % s == 0)


def _tile_rows(chunk: int, step: int) -> int:
    """Rows of a kernel's tile: as many of a grid step's chunks as fill
    the MXU's 128 (the masks shift by a power of two)."""
    if chunk & (chunk - 1):
        return chunk
    return chunk * min(step, max(1, _MXU_ROWS // chunk))


def _kernel_forward(q, k, v, total, beta, chunk, interpret):
    """q, k [B Hk, L, Dk], v [B H, L, Dv], total (``G``), beta [B H, N,
    C] float32 -> (o [B H, L, Dv], the state entering each grid step [B
    H, N / step, Dk, Dv] and the one entering the row's last chunk [B H,
    Dk, Dv], float32)."""
    bh, length, dv = v.shape
    dk, n = q.shape[-1], length // chunk
    group = bh // q.shape[0]
    step = _chunks_a_step(n)
    tile = _tile_rows(chunk, step)
    keyed = pl.BlockSpec((None, step * chunk, dk),
                         lambda b, i: (b // group, i, 0))
    valued = pl.BlockSpec((None, step * chunk, dv), lambda b, i: (b, i, 0))
    whole = pl.BlockSpec((None, length // tile, tile), lambda b, i: (b, 0, 0))
    steps = pl.BlockSpec((None, None, dk, dv), lambda b, i: (b, i, 0, 0))
    last = pl.BlockSpec((None, dk, dv), lambda b, i: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, step=step, chunk=chunk, tile=tile),
        grid=(bh, n // step),
        in_specs=[keyed, keyed, valued, whole, whole],
        out_specs=[valued, steps, last],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((bh, n // step, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        # the state passes from a block of chunks to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_fwd",
    )(q, k, v, total.reshape(bh, -1, tile), beta.reshape(bh, -1, tile))


@jax.named_scope("gated_delta_bwd")
def _kernel_backward(q, k, v, total, beta, steps, do, chunk, interpret):
    """The operands of ``_kernel_forward``, the state entering each of
    its grid steps and ``o``'s cotangent -> those of q, k (a value head
    each: [B H, L, Dk]), v, ``G`` and beta."""
    bh, length, dv = v.shape
    dk, n = q.shape[-1], length // chunk
    group = bh // q.shape[0]
    step = _chunks_a_step(n)
    tile = _tile_rows(chunk, step)
    last = n // step - 1
    keyed = pl.BlockSpec((None, step * chunk, dk),
                         lambda b, i: (b // group, last - i, 0))
    own = pl.BlockSpec((None, step * chunk, dk),
                       lambda b, i: (b, last - i, 0))
    valued = pl.BlockSpec((None, step * chunk, dv),
                          lambda b, i: (b, last - i, 0))
    whole = pl.BlockSpec((None, length // tile, tile), lambda b, i: (b, 0, 0))
    entering = pl.BlockSpec((None, None, dk, dv),
                            lambda b, i: (b, last - i, 0, 0))
    per_head = jax.ShapeDtypeStruct((bh, length, dk), q.dtype)
    tiled = jax.ShapeDtypeStruct((bh, length // tile, tile), jnp.float32)
    dq, dk_, dv_, dtotal, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, step=step, chunk=chunk, tile=tile),
        grid=(bh, n // step),
        in_specs=[keyed, keyed, valued, whole, whole, entering, valued],
        out_specs=[own, own, valued, whole, whole],
        out_shape=[per_head, per_head,
                   jax.ShapeDtypeStruct(v.shape, v.dtype), tiled, tiled],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((step, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_bwd",
    )(q, k, v, total.reshape(tiled.shape), beta.reshape(tiled.shape), steps,
      do)
    return (dq, dk_, dv_, dtotal.reshape(total.shape),
            dbeta.reshape(beta.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule_kernels(q, k, v, total, beta, chunk, interpret):
    # (the names in the primal too: ``models/remat.py`` reads the
    # forward's jaxpr alone, as ``flash_attention._kept_out`` has it)
    return _rule_fwd(q, k, v, total, beta, chunk, interpret)[0]


def _rule_fwd(q, k, v, total, beta, chunk, interpret):
    o, steps, last = _kernel_forward(q, k, v, total, beta, chunk, interpret)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    steps = checkpoint_name(steps, RESIDUAL_NAMES[1])
    return (o, last), (q, k, v, total, beta, steps)


def _rule_bwd(chunk, interpret, res, cotangent):
    q = res[0]
    dq, dk, dv, dtotal, dbeta = _kernel_backward(*res, cotangent[0], chunk,
                                                 interpret)
    if dq.shape != q.shape:             # a key head's value heads, summed
        dq, dk = (jnp.sum(x.reshape(q.shape[0], -1, *q.shape[1:]),
                          axis=1, dtype=jnp.float32).astype(q.dtype)
                  for x in (dq, dk))
    return dq, dk, dv, dtotal, dbeta


_rule_kernels.defvjp(_rule_fwd, _rule_bwd)


def _fused_rule(q, k, v, g, beta, chunk, interpret):
    """The rule as the two kernels; what ``_chunked_rule`` returns."""
    b, length, h, dv = v.shape
    dk = q.shape[-1]

    def heads_first(x):                 # [B, L, H, D] -> [B H, L, D]
        return jnp.moveaxis(x, 2, 1).reshape(-1, length, x.shape[-1])

    def rows(x):                        # [B, L, H] -> [B H, N, C] float32
        return jnp.moveaxis(x.astype(jnp.float32), 2, 1).reshape(
            b * h, length // chunk, chunk)

    o, state = _rule_kernels(
        heads_first(q), heads_first(k), heads_first(v),
        jnp.cumsum(rows(g), axis=-1), rows(beta), chunk, interpret)
    return (jnp.moveaxis(o.reshape(b, h, length, dv), 1, 2),
            state.reshape(b, h, dk, dv))


def kernels_by_default() -> bool:
    """Whether ``gated_delta_rule`` runs as the two kernels where the
    call does not say (``use_pallas=None``): on a TPU.  Elsewhere it is
    the chunked ``jnp`` form with the scan, without a word: a caller that
    needs the kernels asks here."""
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret", "with_state"))
def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = CHUNK,
                     use_pallas: bool | None = None, interpret: bool = False,
                     with_state: bool = False):
    """q, k [B, L, Hk, Dk] (as they enter the rule: normalised and scaled
    by the caller), v [B, L, H, Dv] with ``Hk`` dividing ``H`` (value
    head ``h`` reads key head ``h // (H / Hk)``), g [B, L, H] float32
    log-decays (<= 0), beta [B, L, H] float32 write strengths -> o [B, L,
    H, Dv] in ``v``'s dtype; L a multiple of ``chunk``.  A row is one
    sequence: the state starts at nought at position 0 and crosses
    whatever the row holds.  ``use_pallas`` None: the kernels on a TPU,
    the chunked ``jnp`` form elsewhere; ``interpret`` runs them in the
    Pallas interpreter (CPU tests).  ``with_state``: also the state
    entering each row's last chunk, [B, H, Dk, Dv] float32 (a counter's:
    no gradient)."""
    length, h = v.shape[1:3]
    if length % chunk:
        raise ValueError(f"row of {length} positions in chunks of {chunk}; "
                         "pad upstream")
    if h % q.shape[2] or k.shape != q.shape:
        raise ValueError(f"{q.shape[2]} / {k.shape[2]} heads of q / k for "
                         f"{h} of v")
    if use_pallas is None:
        use_pallas = kernels_by_default()
    if use_pallas:
        if chunk > 16 and chunk & (chunk - 1):
            raise ValueError(f"the kernels halve a chunk down to 16 rows: "
                             f"{chunk} is no power of two")
        o, state = _fused_rule(q, k, v, g, beta, chunk, interpret)
    else:
        o, state = _chunked_rule(q, k, v, g, beta, chunk)
    if with_state:
        return o, jax.lax.stop_gradient(state)
    return o
