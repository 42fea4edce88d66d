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
blocks of four chunks in order), the chunks of a block in an unrolled
loop (a chunk's own parts hang on no state, so they overlap the state
line of the chunk before), ``S`` in a float32 VMEM scratch.  It reads q, k, v and ``g`` where the layer left
them (``[B, L, H D]``: a head's 128 columns through the block index, no
heads-first copy), ``beta`` a head's row of positions, and forms ``G``
itself (a product with a triangle of ones at full precision): no
float32 ``G`` reaches HBM.  It writes ``o`` into ``[B, L, H Dv]`` and the
state entering each grid step.  The gradient is by hand
(``jax.custom_vjp`` over the whole rule): ``kda_bwd`` takes the
forward's grid with the blocks the other way round, walks a block's
chunks forward from the state the forward wrote by the forward's own
lines, holding each chunk's entering state in VMEM, then walks them
backwards carrying ``dS`` in float32 and writes ``dq``, ``dk``, ``dv``,
``dg`` (the reverse running sum of ``dG``, made inside) and ``dbeta``.

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
                                     _to_col, _to_row, unit_lower_inverse)

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
# The kernels: a chunk of 64 rows at a time, its channels along the lanes.

def _masks(chunk: int, dk: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tall = jax.lax.broadcasted_iota(jnp.int32, (chunk, dk), 0)
    return types.SimpleNamespace(
        rows=rows, cols=cols, eye=rows == cols, lower=rows >= cols,
        strict=rows > cols, ones=jnp.where(rows >= cols, 1.0, 0.0),
        eye_k=(jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)),
        tall=tall, chunk=chunk)


def _local(q, k, v, g, b_row, m):
    """A chunk's own rows in VMEM: q, k [C, Dk], v [C, Dv], g [C, Dk]
    float32, b_row (beta) [1, C] float32; ``_chunk_local``'s lines."""
    f32, dt, chunk = _F32, v.dtype, m.chunk
    total = _dot32(m.ones, g)                                # G
    starts = range(0, chunk, BLOCK)
    per_row = jnp.concatenate([jnp.broadcast_to(
        total[a:a + 1], (BLOCK, total.shape[1])) for a in starts], axis=0)
    lo = jnp.exp(total - per_row)
    right = [jnp.exp(jnp.where(m.tall < a + BLOCK, total[a:a + 1] - total,
                               -jnp.inf)) for a in starts]
    kf32, qf32 = k.astype(f32), q.astype(f32)
    lk, lq = kf32 * lo, qf32 * lo
    rk = [kf32 * r for r in right]
    kk = jnp.concatenate([_dot32(lk[a:a + BLOCK], r, _NT)
                          for a, r in zip(starts, rk)], axis=0)
    qk = jnp.concatenate([_dot(lq[a:a + BLOCK].astype(dt), r.astype(dt), _NT)
                          for a, r in zip(starts, rk)], axis=0)
    kk, qk = jnp.where(m.strict, kk, 0.0), jnp.where(m.lower, qk, 0.0)
    b_col = _to_col(b_row, m.eye)
    t = _kernel_inverse(b_col * kk, m.rows, m.cols, chunk)
    tb = t * b_row
    gamma = jnp.exp(total)
    end = total[chunk - 1:chunk]                             # [1, Dk]
    to_end = jnp.exp(end - total)
    ke = kf32 * gamma
    return types.SimpleNamespace(
        lo=lo, right=right, lk=lk, lq=lq, rk=rk, kk=kk, t=t, tb=tb,
        b_row=b_row, b_col=b_col, gamma=gamma, to_end=to_end, ke=ke,
        decay=_to_col(jnp.exp(end), m.eye_k),                # [Dk, 1]
        w=_dot(tb.astype(dt), ke.astype(dt)).astype(dt),
        u=_dot(tb.astype(dt), v).astype(dt),
        p=qk.astype(dt), qg=(qf32 * gamma).astype(dt),
        kd=(kf32 * to_end).astype(dt))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, steps_ref, state,
                *, step: int, chunk: int):
    # Grid (row x head, block of ``step`` chunks, in order).  q_ref,
    # k_ref, g_ref: [step C, Dk]; v_ref, o_ref: [step C, Dv]; b_ref: [N,
    # C] float32, a row's beta a chunk a row, resident across the head's
    # blocks; state: [Dk, Dv] float32; steps_ref: the state entering
    # this grid step.
    dt = v_ref.dtype
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    m = _masks(chunk, q_ref.shape[1])
    steps_ref[...] = state[...]

    def one(c, s):
        at = pl.multiple_of(c * chunk, chunk)
        q, k, v, g = (ref[pl.ds(at, chunk)]
                      for ref in (q_ref, k_ref, v_ref, g_ref))
        x = _local(q, k, v, g, b_ref[pl.ds(blk * step + c, 1), :], m)
        both = _dot(jnp.concatenate([x.w, x.qg], axis=0), s.astype(dt))
        vp = (x.u.astype(_F32) - both[:chunk]).astype(dt)
        o_ref[pl.ds(at, chunk)] = (_dot(x.p, vp) + both[chunk:]).astype(dt)
        return s * x.decay + _dot(x.kd, vp, _TN)

    state[...] = jax.lax.fori_loop(0, step, one, state[...], unroll=True)


def _backward_chunk(q, k, v, do, x, s, ds, m):
    """One chunk's cotangents from its parts ``x`` (``_local``), the
    state entering it ``s`` and the cotangent of the one leaving it
    ``ds`` -> (dq, dk, dv, dg [C, Dk] float32, dbeta [1, C] float32, the
    cotangent of ``s``)."""
    f32, dt, chunk = _F32, v.dtype, m.chunk
    low = ds.astype(dt)
    vp = (x.u.astype(f32) - _dot(x.w, s.astype(dt))).astype(dt)
    dvp = (_dot(x.p, do, _TN) + _dot(x.kd, low)).astype(dt)  # [C, Dv]
    dkd = _dot(vp, low, _NT)                                 # [C, Dk]
    dend = jnp.sum(s * ds, axis=1, keepdims=True) * x.decay  # [Dk, 1]
    ds = ds * x.decay + _dot(jnp.concatenate([x.qg, -x.w], axis=0),
                             jnp.concatenate([do, dvp], axis=0), _TN)
    by_state = _dot(jnp.concatenate([do, dvp], axis=0), s.astype(dt), _NT)
    dqg, dw = by_state[:chunk], -by_state[chunk:]            # [C, Dk]
    dqk = jnp.where(m.lower, _dot(do, vp, _NT), 0.0)
    dtb = _dot(dvp, v, _NT) + _dot(dw.astype(dt), x.ke.astype(dt), _NT)
    dv = _dot(x.tb.astype(dt), dvp, _TN)
    dke = _dot(x.tb.astype(dt), dw.astype(dt), _TN)
    db_row = jnp.sum(dtb * x.t, axis=0, keepdims=True)
    da = -_dot32(x.t, _dot32(dtb * x.b_row, x.t, _NT), _TN)
    da = jnp.where(m.strict, da, 0.0)
    dkk = da * x.b_col
    db_col = jnp.sum(da * x.kk, axis=1, keepdims=True)
    # the decayed pairs, a 16-row block of rows at a time: the row
    # factors' cotangents, the right factors' summed over the blocks
    dlq, dlk, dk_right, dg_right, anchors = [], [], 0.0, 0.0, []
    for i, a in enumerate(range(0, chunk, BLOCK)):
        rows = slice(a, a + BLOCK)
        both = _dot32(jnp.concatenate([dqk[rows], dkk[rows]], axis=0),
                      x.rk[i])
        dlq.append(both[:BLOCK])
        dlk.append(both[BLOCK:])
        drk = _dot32(jnp.concatenate([dqk[rows], dkk[rows]], axis=0),
                     jnp.concatenate([x.lq[rows], x.lk[rows]], axis=0),
                     _TN)                                      # [C, Dk]
        dk_right = dk_right + drk * x.right[i]
        by_right = drk * x.rk[i]
        dg_right = dg_right - by_right
        anchors.append(jnp.sum(by_right, axis=0, keepdims=True))
    dlq, dlk = (jnp.concatenate(parts, axis=0) for parts in (dlq, dlk))
    by_left = dlq * x.lq + dlk * x.lk
    kf32, qf32 = k.astype(f32), q.astype(f32)
    dq = dlq * x.lo + dqg * x.gamma
    dk = dlk * x.lo + dk_right + dke * x.gamma + dkd * x.to_end
    by_end = dkd * kf32 * x.to_end
    dtotal = (by_left + dg_right + dqg * qf32 * x.gamma
              + dke * x.ke - by_end)
    # what each block's anchor row and the chunk's last row take
    first = (m.tall & (BLOCK - 1)) == 0
    anchor_rows = jnp.concatenate([jnp.broadcast_to(
        row - jnp.sum(by_left[a:a + BLOCK], axis=0, keepdims=True),
        (BLOCK, row.shape[1])) for a, row in zip(range(0, chunk, BLOCK),
                                                 anchors)], axis=0)
    dtotal = dtotal + jnp.where(first, anchor_rows, 0.0) + jnp.where(
        m.tall == chunk - 1, jnp.sum(by_end, axis=0, keepdims=True)
        + _to_row(dend, m.eye_k), 0.0)
    dg = _dot32(m.ones, dtotal, _TN)          # the reverse running sum
    db = db_row + _to_row(db_col, m.eye)
    return dq, dk, dv, dg, db, ds


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, do_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, dstate, walked, *, step: int,
                chunk: int):
    # The forward's grid with the blocks, and the chunks of a block, the
    # other way round; dstate [Dk, Dv] float32 is the cotangent of the
    # state LEAVING the chunk at hand.  s_ref: the state entering this
    # grid step as ``kda_fwd`` wrote it; walked [step, Dk, Dv] float32:
    # each of its chunks' entering states, made here by the forward's
    # own lines.  db_ref: beta's cotangent, laid out as b_ref.
    dt = v_ref.dtype
    turn = pl.program_id(1)
    blk = pl.num_programs(1) - 1 - turn

    @pl.when(turn == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    m = _masks(chunk, q_ref.shape[1])

    def parts(c):
        at = pl.multiple_of(c * chunk, chunk)
        q, k, v, g = (ref[pl.ds(at, chunk)]
                      for ref in (q_ref, k_ref, v_ref, g_ref))
        return at, q, k, v, _local(q, k, v, g,
                                   b_ref[pl.ds(blk * step + c, 1), :], m)

    def forward(c, s):
        walked[c] = s
        _, _, _, _, x = parts(c)
        vp = (x.u.astype(_F32) - _dot(x.w, s.astype(dt))).astype(dt)
        return s * x.decay + _dot(x.kd, vp, _TN)

    walked[step - 1] = jax.lax.fori_loop(0, step - 1, forward, s_ref[...],
                                         unroll=True)

    def backward(i, ds):
        c = step - 1 - i
        at, q, k, v, x = parts(c)
        dq, dk, dv, dg, db, ds = _backward_chunk(
            q, k, v, do_ref[pl.ds(at, chunk)], x, walked[c], ds, m)
        dq_ref[pl.ds(at, chunk)] = dq.astype(dt)
        dk_ref[pl.ds(at, chunk)] = dk.astype(dt)
        dv_ref[pl.ds(at, chunk)] = dv.astype(dt)
        dg_ref[pl.ds(at, chunk)] = dg
        db_ref[pl.ds(blk * step + c, 1), :] = db
        return ds

    dstate[...] = jax.lax.fori_loop(0, step, backward, dstate[...],
                                    unroll=True)


def _chunks_a_step(n: int) -> int:
    return next(s for s in _CHUNKS_A_STEP if n % s == 0)


def _params():
    # the state passes from a block of chunks to the next
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _specs(heads, step, chunk, dk, dv, n, reverse=False):
    last = n // step - 1

    def blk(i):
        return last - i if reverse else i

    def head(width):
        return pl.BlockSpec((None, step * chunk, width),
                            lambda b, i: (b // heads, blk(i), b % heads))

    return types.SimpleNamespace(
        keyed=head(dk), valued=head(dv),
        whole=pl.BlockSpec((None, n, chunk), lambda b, i: (b, 0, 0)),
        steps=pl.BlockSpec((None, None, dk, dv),
                           lambda b, i: (b, blk(i), 0, 0)))


def _kernel_forward(q, k, v, g, beta, heads, chunk, interpret):
    """q, k, g [B, L, H Dk], v [B, L, H Dv], beta [B H, N, C] float32 ->
    (o [B, L, H Dv], the state entering each grid step [B H, N / step,
    Dk, Dv] float32)."""
    bsz, length = v.shape[:2]
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    n = length // chunk
    step = _chunks_a_step(n)
    s = _specs(heads, step, chunk, dk, dv, n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, step=step, chunk=chunk),
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
    n = length // chunk
    step = _chunks_a_step(n)
    s = _specs(heads, step, chunk, dk, dv, n, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, step=step, chunk=chunk),
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

    rows = jnp.moveaxis(beta.astype(_F32), 2, 1).reshape(
        b * h, length // chunk, chunk)
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
