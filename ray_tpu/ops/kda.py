"""Kimi Delta Attention's rule (KDA): the gated delta rule whose decay is
a vector a head and position, one rate per key channel, chunked, as two
Pallas kernels that hold a chunk's work in VMEM.

Per head a float32 state ``S [Dk, Dv]`` starts at nought at the row's
start and at every position ``t`` decays channel by channel, takes a
rank-one correction towards ``v_t`` along ``k_t`` and is read by ``q_t``:

    S <- Diag(exp(g_t)) S                 g_t in [LOWER, 0]^Dk
    S <- S + k_t (beta_t (v_t - S^T k_t))^T
    o_t = S^T q_t

In chunks of ``C`` positions (64), with ``G [C, Dk]`` the running sum of
``g`` inside a chunk (channel by channel) and ``S`` the state entering
it, every pair of positions decays by ``exp(G_i - G_j)`` on each channel:

    KK_ij = sum_c k_ic k_jc exp(G_ic - G_jc)       QK_ij likewise, q_i
    A   = diag(beta) KK, strictly lower;  T = (I + A)^-1   float32
    W   = T diag(beta) (K . exp(G))       U = T diag(beta) V
    V'  = U - W S
    O   = (QK, lower with the diagonal) V' + (Q . exp(G)) S
    S  <- Diag(exp(G_C)) S + (K . exp(G_C - G))^T V'

The decay is no ``[C, C]`` mask here (``ops/gated_delta.py``'s is): it
sits inside the products.  ``KK`` and ``QK`` are made by 16-row blocks
``a`` of their rows, each factored about the block's first row ``G_a``:

    KK_ij = (K_i . exp(G_i - G_a)) . (K_j . exp(G_a - G_j))

For ``j`` in an earlier block both exponents are <= 0.  In ``i``'s own
block the right one is >= 0, and at most ``-LOWER * 15 = 75``: ``exp(75)``
is below float32's largest value (``exp(88.7)``).  That is what the
gate's lower bound is for; the rule relies on it and refuses no input
for it (``g`` below ``LOWER`` may overflow).  ``KK`` is made from float32
factors at full precision (it goes into the inverse), ``QK`` from factors
in the inputs' dtype; the inverse is ``ops/gated_delta.py``'s own.  The
backward makes the pairs' gradients from float32 factors at full
precision too: ``dG`` is a difference of their terms (each block's
first row takes what its rows give), and factors rounded to bfloat16
left ``dg`` four times as far from the recurrence as ``dq``.

On a TPU the rule runs inside ``kda_fwd``: a grid over (row x head,
blocks of four chunks in order), ``S`` in a float32 VMEM scratch.  A
grid step works on tiles of two chunks side by side (128 rows, the
MXU's width; one chunk of 64 where the row's chunk count is odd), every
``[128, 128]`` operand block-diagonal by chunk, so that one product
serves both chunks: ``G`` (a product with a block of triangles of
ones at full precision: no float32 ``G`` reaches HBM), ``KK`` and ``QK``
(block ``a``'s rows of both chunks stacked against one right factor
whose rows carry their own chunk's anchor ``G_a``), the inverse
(``ops/gated_delta.py``'s, on the tile), ``W`` and ``U``.  The state
then walks the tile's chunks one by one.  It reads q, k, v and ``g``
where the layer left them (``[B, L, H D]``: a head's 128 columns
through the block index, no heads-first copy) and ``beta`` a head's
row of positions, a tile a row; it writes ``o`` into ``[B, L, H Dv]``
and the state entering each grid step.  The gradient is by hand
(``jax.custom_vjp`` over the whole rule): ``kda_bwd`` takes the
forward's grid with the blocks the other way round, makes each tile's
own parts once, walks the step's chunks forward from the state the
forward wrote by the forward's own lines, holding each chunk's entering
state in VMEM, then walks the tiles backwards carrying ``dS`` in
float32 and writes ``dq``, ``dk``, ``dv``, ``dg`` (the reverse running
sum of ``dG``, made inside) and ``dbeta``.

Off the TPU, and under ``use_pallas=False``, the same lines are batched
``jnp`` differentiated by JAX (the inverse by hand) around a
``lax.scan`` over chunks (``_chunked_rule``): the oracle the kernels are
held to in interpret mode; ``fallback_passes`` says when it runs.

``o`` and the step states carry the names ``RESIDUAL_NAMES``; a layer
rematerialised under ``models.transformer.remat_layer`` keeps both
(``models/remat.py``: ``BASE_NAMES``) and so runs the forward kernel
once a layer.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.gated_delta import (_NT, _TN, _dot, _dot32, _kernel_inverse,
                                     _stack, _tile_rows, _to_col, _to_row,
                                     unit_lower_inverse)

CHUNK = 64
#: The rows a decay pair is factored over: ``-LOWER * (BLOCK - 1)`` is
#: the largest exponent the rule makes.
BLOCK = 16
#: The least log-decay a position may have on a channel.
LOWER = -5.0
#: What the forward kernel writes for the backward pass: ``o`` [B, L,
#: H Dv] in the inputs' dtype and the state entering each grid step [B H,
#: N / step, Dk, Dv] float32.
RESIDUAL_NAMES = ("kda_out", "kda_step_states")
_CHUNKS_A_STEP = (4, 2, 1)
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


# --------------------------------------------------------------------------
# The chunked form in ``jnp``: [..., C, D] a chunk, any leading axes.

def _anchored(g_total, chunk):
    """-> (exp(G - G_a) [.., C, Dk] with ``a`` the first row of each
    row's block, the right factors' decays [.., C / BLOCK, C, Dk]:
    exp(G_a - G_j) for ``j`` up to the end of block ``a``, 0 after)."""
    n_blocks = chunk // BLOCK
    anchors = g_total[..., ::BLOCK, :]                       # [.., nb, Dk]
    per_row = jnp.repeat(anchors, BLOCK, axis=-2)
    ends = (jnp.arange(n_blocks) + 1) * BLOCK
    within = jnp.arange(chunk)[None, :] < ends[:, None]      # [nb, C]
    right = jnp.exp(jnp.where(within[..., None],
                              anchors[..., :, None, :]
                              - g_total[..., None, :, :], -jnp.inf))
    return jnp.exp(g_total - per_row), right


def _pairs(left, right, dtype, precision):
    """sum_c left_ic right_b(i) jc [.., C, C] from the row factors
    [.., C, Dk] and each block's right factors [.., nb, C, Dk]."""
    nb, chunk = right.shape[-3:-1]
    rows = left.reshape(*left.shape[:-2], nb, BLOCK, left.shape[-1])
    out = jnp.einsum("...bik,...bjk->...bij", rows.astype(dtype),
                     right.astype(dtype), precision=precision,
                     preferred_element_type=_F32)
    return out.reshape(*out.shape[:-3], chunk, chunk)


def _chunk_local(q, k, v, g, beta):
    """What a chunk makes of its own rows: q, k [.., C, Dk], v [.., C,
    Dv] in the inputs' dtype, g [.., C, Dk] and beta [.., C] float32."""
    dt = v.dtype
    chunk = v.shape[-2]
    total = jnp.cumsum(g, axis=-2)                           # G
    lo, right = _anchored(total, chunk)
    kf32, qf32 = k.astype(_F32), q.astype(_F32)
    rk = kf32[..., None, :, :] * right
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(lower, -1)
    kk = jnp.where(strict, _pairs(kf32 * lo, rk, _F32, _HIGHEST), 0.0)
    qk = jnp.where(lower, _pairs(qf32 * lo, rk, dt, None), 0.0)
    t = unit_lower_inverse(beta[..., :, None] * kk)
    tb = (t * beta[..., None, :]).astype(dt)
    gamma = jnp.exp(total)
    end = total[..., -1:, :]
    w = jnp.einsum("...ij,...jk->...ik", tb, (kf32 * gamma).astype(dt),
                   preferred_element_type=_F32).astype(dt)
    u = jnp.einsum("...ij,...jv->...iv", tb, v,
                   preferred_element_type=_F32).astype(dt)
    return types.SimpleNamespace(
        w=w, u=u, p=qk.astype(dt), qg=(qf32 * gamma).astype(dt),
        kd=(kf32 * jnp.exp(end - total)).astype(dt),
        decay=jnp.exp(end[..., 0, :]))                       # [.., Dk]


def _chunks(x, chunk):
    """[B, L, H, ...] -> [B, H, L // chunk, chunk, ...]."""
    b, length, h = x.shape[:3]
    x = x.reshape(b, length // chunk, chunk, h, *x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


def _chunked_rule(q, k, v, g, beta, chunk):
    """The rule as batched ``jnp`` around the scan over chunks -> o [B,
    L, H, Dv] in ``v``'s dtype."""
    b, length, h, dv = v.shape
    dt = v.dtype
    x = _chunk_local(*(_chunks(a, chunk) for a in (q, k, v)),
                     _chunks(g.astype(_F32), chunk),
                     _chunks(beta.astype(_F32), chunk))

    def step(s, at):
        w, u, qg, kd, decay = at
        low = s.astype(dt)
        vp = (u.astype(_F32) - jnp.einsum(
            "bhck,bhkv->bhcv", w, low, preferred_element_type=_F32)
              ).astype(dt)
        read = jnp.einsum("bhck,bhkv->bhcv", qg, low,
                          preferred_element_type=_F32)
        s = s * decay[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", kd, vp, preferred_element_type=_F32)
        return s, (vp, read)

    zero = jnp.zeros((b, h, q.shape[-1], dv), _F32)
    _, (vp, read) = jax.lax.scan(step, zero, tuple(
        jnp.moveaxis(a, 2, 0) for a in (x.w, x.u, x.qg, x.kd, x.decay)))
    vp, read = (jnp.moveaxis(a, 0, 2) for a in (vp, read))
    o = jnp.einsum("...ij,...jv->...iv", x.p, vp,
                   preferred_element_type=_F32) + read
    return jnp.moveaxis(o.astype(dt), 1, 3).reshape(b, length, h, dv)


# --------------------------------------------------------------------------
# The kernels: a tile of ``R`` rows at a time, its channels along the
# lanes.  ``R`` is two chunks side by side (128 rows, the MXU's width)
# where a grid step holds two or more, one chunk where the row's chunk
# count is odd (``gated_delta._tile_rows``).  Every ``[R, R]`` operand is
# block-diagonal by chunk, so one product serves the tile's chunks.

def _masks(tile: int, chunk: int, dk: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (tile, dk), 0)
    lower, strict = rows >= cols, rows > cols
    if tile > chunk:                    # chunk is a power of two
        shift = chunk.bit_length() - 1
        same = (rows >> shift) == (cols >> shift)
        lower, strict, pos = same & lower, same & strict, pos & (chunk - 1)
    return types.SimpleNamespace(
        rows=rows, cols=cols, eye=rows == cols, lower=lower, strict=strict,
        ones=jnp.where(lower, 1.0, 0.0),
        eye_k=(jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)),
        pos=pos,                        # a row's place in its chunk
        firsts=range(0, tile, chunk),   # each chunk's first row
        starts=range(0, chunk, BLOCK),  # each block's, in a chunk
        chunk=chunk, tile=tile)


def _spread(rows, n):
    """Each row [1, D] of ``rows`` over ``n`` rows, stacked."""
    return _stack([jnp.broadcast_to(r, (n, r.shape[1])) for r in rows])


def _blocks(x, a, m):
    """Block ``a`` of every chunk of the tile, stacked: [R / C 16, D]."""
    return _stack([x[c + a:c + a + BLOCK] for c in m.firsts])


def _pairs_of(left, right, m, dot):
    """The decayed pairs [R, R] by blocks: for each block ``a``, its rows
    in every chunk against one right factor whose rows carry their own
    chunk's anchor; a chunk's rows keep their own chunk's columns (the
    caller's mask drops the others)."""
    out = [dot(_blocks(left, a, m), r, _NT) for a, r in zip(m.starts, right)]
    return _stack([part[i * BLOCK:(i + 1) * BLOCK]
                   for i in range(len(m.firsts)) for part in out])


def _local(q, k, v, g, b_row, m):
    """A tile's own rows in VMEM: q, k [R, Dk], v [R, Dv], g [R, Dk]
    float32, b_row (beta) [1, R] float32; ``_chunk_local``'s lines for
    each of its chunks."""
    f32, dt, chunk = _F32, v.dtype, m.chunk
    total = _dot32(m.ones, g)                                # G
    lo = jnp.exp(total - _spread(
        [total[a:a + 1] for a in range(0, m.tile, BLOCK)], BLOCK))
    right = [jnp.exp(jnp.where(
        m.pos < a + BLOCK,
        _spread([total[c + a:c + a + 1] for c in m.firsts], chunk) - total,
        -jnp.inf)) for a in m.starts]
    kf32, qf32 = k.astype(f32), q.astype(f32)
    lk, lq = kf32 * lo, qf32 * lo
    rk = [kf32 * r for r in right]
    kk = jnp.where(m.strict, _pairs_of(lk, rk, m, _dot32), 0.0)
    qk = jnp.where(m.lower, _pairs_of(lq.astype(dt),
                                      [r.astype(dt) for r in rk], m, _dot),
                   0.0)
    b_col = _to_col(b_row, m.eye)
    t = _kernel_inverse(b_col * kk, m.rows, m.cols, chunk)
    tb = t * b_row
    gamma = jnp.exp(total)
    ends = [total[c + chunk - 1:c + chunk] for c in m.firsts]   # [1, Dk]
    to_end = jnp.exp(_spread(ends, chunk) - total)
    ke = kf32 * gamma
    return types.SimpleNamespace(
        lo=lo, right=right, lk=lk, lq=lq, rk=rk, kk=kk, t=t, tb=tb,
        b_row=b_row, b_col=b_col, gamma=gamma, to_end=to_end, ke=ke,
        decay=[_to_col(jnp.exp(end), m.eye_k) for end in ends],  # [Dk, 1]
        w=_dot(tb.astype(dt), ke.astype(dt)).astype(dt),
        u=_dot(tb.astype(dt), v).astype(dt),
        p=qk.astype(dt), qg=(qf32 * gamma).astype(dt),
        kd=(kf32 * to_end).astype(dt))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, steps_ref, state,
                *, step: int, chunk: int, tile: int):
    # Grid (row x head, block of ``step`` chunks, in order).  q_ref,
    # k_ref, g_ref: [step C, Dk]; v_ref, o_ref: [step C, Dv]; b_ref: [N C
    # / R, R] float32, a row's beta a tile a row, resident across the
    # head's blocks; state: [Dk, Dv] float32; steps_ref: the state
    # entering this grid step.
    f32, dt = _F32, v_ref.dtype
    blk = pl.program_id(1)
    tiles = step * chunk // tile

    @pl.when(blk == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    m = _masks(tile, chunk, q_ref.shape[1])
    steps_ref[...] = state[...]

    def one(j, s):
        at = pl.ds(pl.multiple_of(j * tile, tile), tile)
        x = _local(q_ref[at], k_ref[at], v_ref[at], g_ref[at],
                   b_ref[pl.ds(blk * tiles + j, 1), :], m)
        written, read = [], []
        for i, c in enumerate(m.firsts):
            rows = slice(c, c + chunk)
            # W S and (gamma Q) S: the state is the MXU's operand once
            both = _dot(jnp.concatenate([x.w[rows], x.qg[rows]], axis=0),
                        s.astype(dt))
            written.append((x.u[rows].astype(f32) - both[:chunk]).astype(dt))
            read.append(both[chunk:])
            s = s * x.decay[i] + _dot(x.kd[rows], written[-1], _TN)
        o_ref[at] = (_dot(x.p, _stack(written)) + _stack(read)).astype(dt)
        return s

    state[...] = jax.lax.fori_loop(0, tiles, one, state[...], unroll=True)


def _backward_tile(q, k, v, do, x, vp, entering, ds, m):
    """A tile's cotangents from its parts ``x`` (``_local``), its V' [R,
    Dv], the states entering its chunks and the cotangent of the one
    leaving it ``ds`` -> (dq, dk, dv, dg [R, Dk] float32, dbeta [1, R]
    float32, the cotangent of the state entering it)."""
    f32, dt, chunk, tile = _F32, v.dtype, m.chunk, m.tile
    # the two lines that hold the state, transposed, a chunk at a time,
    # the last first
    from_o = _dot(x.p, do, _TN)                              # P^T dO
    n = len(m.firsts)
    dvp, dkd, dend, by_state = [None] * n, [None] * n, [None] * n, [None] * n
    for i in reversed(range(n)):
        at, low = slice(m.firsts[i], m.firsts[i] + chunk), ds.astype(dt)
        dvp[i] = (from_o[at] + _dot(x.kd[at], low)).astype(dt)
        dkd[i] = _dot(vp[at], low, _NT)                      # [C, Dk]
        dend[i] = jnp.sum(entering[i] * ds, axis=1,
                          keepdims=True) * x.decay[i]        # [Dk, 1]
        by_o = jnp.concatenate([do[at], dvp[i]], axis=0)
        ds = ds * x.decay[i] + _dot(
            jnp.concatenate([x.qg[at], -x.w[at]], axis=0), by_o, _TN)
        # dO S^T and dV' S^T: the state is the MXU's operand once
        by_state[i] = _dot(by_o, entering[i].astype(dt), _NT)
    dqg = _stack([b[:chunk] for b in by_state])              # [R, Dk]
    dw = _stack([-b[chunk:] for b in by_state])
    dvp, dkd = _stack(dvp), _stack(dkd)
    dqk = jnp.where(m.lower, _dot(do, vp, _NT), 0.0)
    dtb = _dot(dvp, v, _NT) + _dot(dw.astype(dt), x.ke.astype(dt), _NT)
    dv = _dot(x.tb.astype(dt), dvp, _TN)
    dke = _dot(x.tb.astype(dt), dw.astype(dt), _TN)
    db_row = jnp.sum(dtb * x.t, axis=0, keepdims=True)
    da = -_dot32(x.t, _dot32(dtb * x.b_row, x.t, _NT), _TN)
    da = jnp.where(m.strict, da, 0.0)
    dkk = da * x.b_col
    db_col = jnp.sum(da * x.kk, axis=1, keepdims=True)
    # the decayed pairs, block ``a`` of every chunk at a time: the row
    # factors' cotangents, the right factors' summed over the blocks
    dlq, dlk, dk_right, dg_right, anchors = {}, {}, 0.0, 0.0, {}
    for a, right, rk in zip(m.starts, x.right, x.rk):
        grads = jnp.concatenate([_blocks(dqk, a, m), _blocks(dkk, a, m)],
                                axis=0)                      # [2 R / C 16, R]
        both = _dot32(grads, rk)
        drk = _dot32(grads, jnp.concatenate(
            [_blocks(x.lq, a, m), _blocks(x.lk, a, m)], axis=0), _TN)
        dk_right = dk_right + drk * right
        by_right = drk * rk
        dg_right = dg_right - by_right
        for i, c in enumerate(m.firsts):
            dlq[c + a] = both[i * BLOCK:(i + 1) * BLOCK]
            dlk[c + a] = both[(n + i) * BLOCK:(n + i + 1) * BLOCK]
            anchors[c + a] = jnp.sum(by_right[c:c + chunk], axis=0,
                                     keepdims=True)
    dlq, dlk = (_stack([d[r] for r in range(0, tile, BLOCK)])
                for d in (dlq, dlk))
    by_left = dlq * x.lq + dlk * x.lk
    kf32, qf32 = k.astype(f32), q.astype(f32)
    dq = dlq * x.lo + dqg * x.gamma
    dk = dlk * x.lo + dk_right + dke * x.gamma + dkd * x.to_end
    by_end = dkd * kf32 * x.to_end
    dtotal = (by_left + dg_right + dqg * qf32 * x.gamma
              + dke * x.ke - by_end)
    # what each block's anchor row and each chunk's last row take
    anchor_rows = _spread([
        anchors[r] - jnp.sum(by_left[r:r + BLOCK], axis=0, keepdims=True)
        for r in range(0, tile, BLOCK)], BLOCK)
    end_rows = _spread([
        jnp.sum(by_end[c:c + chunk], axis=0, keepdims=True)
        + _to_row(dend[i], m.eye_k) for i, c in enumerate(m.firsts)], chunk)
    dtotal = (dtotal + jnp.where((m.pos & (BLOCK - 1)) == 0, anchor_rows, 0.0)
              + jnp.where(m.pos == chunk - 1, end_rows, 0.0))
    dg = _dot32(m.ones, dtotal, _TN)          # the reverse running sum
    db = db_row + _to_row(db_col, m.eye)
    return dq, dk, dv, dg, db, ds


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, dstate, walked, *, step: int,
                chunk: int, tile: int):
    # The forward's grid with the blocks, and the tiles of a block, the
    # other way round; dstate [Dk, Dv] float32 is the cotangent of the
    # state LEAVING the tile at hand.  s_ref: the state entering this
    # grid step as ``kda_fwd`` wrote it; walked [step, Dk, Dv] float32:
    # each of its chunks' entering states, made here by the forward's
    # own lines.  db_ref: beta's cotangent, laid out as b_ref.
    f32, dt = _F32, v_ref.dtype
    turn = pl.program_id(1)
    blk = pl.num_programs(1) - 1 - turn
    tiles, per = step * chunk // tile, tile // chunk

    @pl.when(turn == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    m = _masks(tile, chunk, q_ref.shape[1])
    here = [slice(j * tile, (j + 1) * tile) for j in range(tiles)]
    # each tile's own parts, once
    local = [_local(q_ref[at], k_ref[at], v_ref[at], g_ref[at],
                    b_ref[pl.ds(blk * tiles + j, 1), :], m)
             for j, at in enumerate(here)]
    # the forward's walk through the step's chunks: its lines, its
    # casts, its order, so the states are the forward's
    s, wrote = s_ref[...], []
    for j, x in enumerate(local):
        written = []
        for i, c in enumerate(m.firsts):
            rows = slice(c, c + chunk)
            walked[j * per + i] = s
            written.append((x.u[rows].astype(f32)
                            - _dot(x.w[rows], s.astype(dt))).astype(dt))
            if (j, i) != (tiles - 1, per - 1):
                s = s * x.decay[i] + _dot(x.kd[rows], written[-1], _TN)
        wrote.append(_stack(written))
    ds = dstate[...]
    for j in reversed(range(tiles)):
        at = here[j]
        dq, dk, dv, dg, db, ds = _backward_tile(
            q_ref[at], k_ref[at], v_ref[at], do_ref[at], local[j], wrote[j],
            [walked[j * per + i] for i in range(per)], ds, m)
        dq_ref[at] = dq.astype(dt)
        dk_ref[at] = dk.astype(dt)
        dv_ref[at] = dv.astype(dt)
        dg_ref[at] = dg
        db_ref[pl.ds(blk * tiles + j, 1), :] = db
    dstate[...] = ds


def _layout(length: int, chunk: int):
    """-> (chunks a row, chunks a grid step, rows a tile)."""
    n = length // chunk
    step = next(s for s in _CHUNKS_A_STEP if n % s == 0)
    return n, step, _tile_rows(chunk, step)


def _params():
    # the state passes from a block of chunks to the next
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _specs(heads, step, chunk, tile, dk, dv, n, reverse=False):
    last = n // step - 1

    def blk(i):
        return last - i if reverse else i

    def head(width):
        return pl.BlockSpec((None, step * chunk, width),
                            lambda b, i: (b // heads, blk(i), b % heads))

    return types.SimpleNamespace(
        keyed=head(dk), valued=head(dv),
        whole=pl.BlockSpec((None, n * chunk // tile, tile),
                           lambda b, i: (b, 0, 0)),
        steps=pl.BlockSpec((None, None, dk, dv),
                           lambda b, i: (b, blk(i), 0, 0)))


def _kernel_forward(q, k, v, g, beta, heads, chunk, interpret):
    """q, k, g [B, L, H Dk], v [B, L, H Dv], beta [B H, L / R, R]
    float32 -> (o [B, L, H Dv], the state entering each grid step [B H,
    N / step, Dk, Dv] float32)."""
    bsz, length = v.shape[:2]
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    n, step, tile = _layout(length, chunk)
    s = _specs(heads, step, chunk, tile, dk, dv, n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, step=step, chunk=chunk, tile=tile),
        grid=(bsz * heads, n // step),
        in_specs=[s.keyed, s.keyed, s.valued, s.keyed, s.whole],
        out_specs=[s.valued, s.steps],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((bsz * heads, n // step, dk, dv),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=_params(), interpret=interpret, name="kda_fwd",
    )(q, k, v, g, beta)


def _kernel_backward(q, k, v, g, beta, steps, do, heads, chunk, interpret):
    """The operands of ``_kernel_forward``, the state entering each of
    its grid steps and ``o``'s cotangent -> those of q, k, v, g, beta."""
    bsz, length = v.shape[:2]
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    n, step, tile = _layout(length, chunk)
    s = _specs(heads, step, chunk, tile, dk, dv, n, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, step=step, chunk=chunk, tile=tile),
        grid=(bsz * heads, n // step),
        in_specs=[s.keyed, s.keyed, s.valued, s.keyed, s.whole, s.steps,
                  s.valued],
        out_specs=[s.keyed, s.keyed, s.valued, s.keyed, s.whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32),
                        pltpu.VMEM((step, dk, dv), _F32)],
        compiler_params=_params(), interpret=interpret, name="kda_bwd",
    )(q, k, v, g, beta, steps, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _rule_kernels(q, k, v, g, beta, heads, chunk, interpret):
    # (the names in the primal too: ``models/remat.py`` reads the
    # forward's jaxpr alone)
    return _rule_fwd(q, k, v, g, beta, heads, chunk, interpret)[0]


def _rule_fwd(q, k, v, g, beta, heads, chunk, interpret):
    o, steps = _kernel_forward(q, k, v, g, beta, heads, chunk, interpret)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    steps = checkpoint_name(steps, RESIDUAL_NAMES[1])
    return o, (q, k, v, g, beta, steps)


def _rule_bwd(heads, chunk, interpret, res, do):
    return _kernel_backward(*res, do, heads, chunk, interpret)


_rule_kernels.defvjp(_rule_fwd, _rule_bwd)


def _fused_rule(q, k, v, g, beta, chunk, interpret):
    b, length, h, dv = v.shape

    def flat(x):                        # [B, L, H, D] -> [B, L, H D]: free
        return x.reshape(b, length, -1)

    tile = _layout(length, chunk)[2]
    rows = jnp.moveaxis(beta.astype(_F32), 2, 1).reshape(
        b * h, length // tile, tile)
    o = _rule_kernels(flat(q), flat(k), flat(v), flat(g.astype(_F32)), rows,
                      h, chunk, interpret)
    return o.reshape(b, length, h, dv)


def kernels_by_default() -> bool:
    """Whether ``kda_rule`` runs as the two kernels where the call does
    not say (``use_pallas=None``): on a TPU."""
    return jax.default_backend() == "tpu"


def fallback_passes() -> int:
    """The counter ``kda_fallback_passes`` of a layer's rule: 1 where it
    runs as the chunked ``jnp`` form, 0 where as the kernels."""
    return 0 if kernels_by_default() else 1


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret"))
def kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
             beta: jax.Array, chunk: int = CHUNK,
             use_pallas: bool | None = None,
             interpret: bool = False) -> jax.Array:
    """q, k [B, L, H, Dk] (as they enter the rule: normalised and scaled
    by the caller), v [B, L, H, Dv], g [B, L, H, Dk] float32 log-decays
    in ``[LOWER, 0]``, beta [B, L, H] float32 write strengths -> o [B, L,
    H, Dv] in ``v``'s dtype; L a multiple of ``chunk`` (itself a multiple
    of ``BLOCK``).  A row is one sequence: the state starts at nought at
    position 0 and crosses whatever the row holds.  ``use_pallas`` None:
    the kernels on a TPU, the chunked ``jnp`` form elsewhere;
    ``interpret`` runs them in the Pallas interpreter (CPU tests)."""
    length = v.shape[1]
    if length % chunk or chunk % BLOCK:
        raise ValueError(f"row of {length} positions in chunks of {chunk} "
                         f"(a multiple of {BLOCK}); pad upstream")
    if k.shape != q.shape or g.shape != q.shape or q.shape[:3] != v.shape[:3]:
        raise ValueError(f"q {q.shape}, k {k.shape}, g {g.shape} beside v "
                         f"{v.shape}: one key head a value head")
    if use_pallas is None:
        use_pallas = kernels_by_default()
    if use_pallas:
        return _fused_rule(q, k, v, g, beta, chunk, interpret)
    return _chunked_rule(q, k, v, g, beta, chunk)
