"""The gated delta rule (Gated DeltaNet's linear attention), chunked, with
the pass over the recurrent state as two Pallas kernels.

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

Everything but the two lines that hold ``S`` is independent over chunks:
batched ``jnp`` products, differentiated by JAX (the inverse by hand:
``dA = -T^T dT T^T``).  The pass over the state, ``V' = U - W S`` then
``S <- c S + Kd^T V'`` chunk after chunk, is the kernel
``gated_delta_fwd``: a grid over (row x head) and blocks of chunks in
order, ``S`` in a float32 VMEM scratch, emitting every chunk's entering
state and ``V'``; its transpose ``gated_delta_bwd`` walks the chunks
backwards carrying ``dS``.  The MXU gets its operands in the inputs'
dtype with float32 accumulation; the state, the decays and the inverse
are float32.  No exponent is ever positive and nothing is divided by a
decay, so a head that forgets within a position (``g`` of -30) and one
that never does run alike.

Off the TPU the state pass is the same recurrence as a ``lax.scan`` over
chunks (``_state_pass_scan``), which is also what the kernels are held
to in interpret mode.

Under ``models.transformer.remat_layer`` nothing of this is kept: the
layer's backward runs the forward kernel again (2.7 ms a call on the
v5e at [2 x 32 heads, 8,192, 128], 8 ms of a 677 ms step over three
layers; keeping the states and ``V'`` would hold 0.6 GB a layer:
PERF.md section 6, PR 35).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
# Chunks a grid step: the step's fixed cost is paid once for all of
# them, and their blocks are fetched together.
_CHUNKS_A_STEP = (8, 4, 2, 1)
_HIGHEST = jax.lax.Precision.HIGHEST
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


def _fwd_kernel(w_ref, u_ref, kd_ref, c_ref, s_ref, vp_ref, state, *,
                step: int):
    # Grid (row x head, block of ``step`` chunks, in order).  w_ref,
    # kd_ref: [step, C, Dk]; u_ref, vp_ref: [step, C, Dv]; c_ref:
    # [N, Dv], a chunk's decay along a row, resident across the head's
    # blocks; s_ref: [step, Dk, Dv] float32; state: [Dk, Dv] float32.
    f32 = jnp.float32
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    s = state[...]
    for j in range(step):
        s_ref[j] = s
        w, kd = w_ref[j], kd_ref[j]
        vp = (u_ref[j].astype(f32) - jnp.dot(
            w, s.astype(w.dtype), preferred_element_type=f32)
              ).astype(vp_ref.dtype)
        vp_ref[j] = vp
        s = s * c_ref[pl.ds(blk * step + j, 1), :] + jax.lax.dot_general(
            kd, vp, _TN, preferred_element_type=f32)
    state[...] = s


def _bwd_kernel(w_ref, kd_ref, c_ref, s_ref, vp_ref, dvp_ref, ds_ref,
                du_ref, dw_ref, dkd_ref, dc_ref, dstate, *, step: int):
    # The forward's grid with the blocks, and the chunks of a block, the
    # other way round; dstate [Dk, Dv] float32 is the cotangent of the
    # state LEAVING the chunk at hand.  ds_ref: the cotangent of the
    # entering states as emitted, [step, Dk, Dv] float32; dc_ref [N, Dv]:
    # a chunk's decay's cotangent, still to be summed along its row.
    f32 = jnp.float32
    turn = pl.program_id(1)
    blk = pl.num_programs(1) - 1 - turn

    @pl.when(turn == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    ds = dstate[...]
    for j in reversed(range(step)):
        w, kd, vp, s = w_ref[j], kd_ref[j], vp_ref[j], s_ref[j]
        row = pl.ds(blk * step + j, 1)
        low = ds.astype(w.dtype)
        dvp = dvp_ref[j].astype(f32) + jnp.dot(kd, low,
                                               preferred_element_type=f32)
        dkd_ref[j] = jax.lax.dot_general(
            vp, low, _NT, preferred_element_type=f32).astype(dkd_ref.dtype)
        dc_ref[row, :] = jnp.sum(s * ds, axis=0, keepdims=True)
        du_ref[j] = dvp.astype(du_ref.dtype)
        dvp = dvp.astype(w.dtype)
        dw_ref[j] = (-jax.lax.dot_general(
            dvp, s.astype(w.dtype), _NT,
            preferred_element_type=f32)).astype(dw_ref.dtype)
        ds = ds * c_ref[row, :] + ds_ref[j] - jax.lax.dot_general(
            w, dvp, _TN, preferred_element_type=f32)
    dstate[...] = ds


def _chunks_a_step(n: int) -> int:
    return next(s for s in _CHUNKS_A_STEP if n % s == 0)


def _kernel_forward(w, u, kd, c, interpret):
    bh, n, chunk, dk = w.shape
    dv = u.shape[-1]
    step = _chunks_a_step(n)

    def blocked(rows, width):
        return pl.BlockSpec((None, step, rows, width),
                            lambda b, i: (b, i, 0, 0))

    return pl.pallas_call(
        functools.partial(_fwd_kernel, step=step),
        grid=(bh, n // step),
        in_specs=[blocked(chunk, dk), blocked(chunk, dv), blocked(chunk, dk),
                  pl.BlockSpec((None, n, dv), lambda b, i: (b, 0, 0))],
        out_specs=[blocked(dk, dv), blocked(chunk, dv)],
        out_shape=[jax.ShapeDtypeStruct((bh, n, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct(u.shape, u.dtype)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        # the state passes from a block of chunks to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_fwd",
    )(w, u, kd, jnp.broadcast_to(c[..., None], (bh, n, dv)))


@jax.named_scope("gated_delta_bwd")
def _kernel_backward(w, kd, c, states, vp, dstates, dvp, interpret):
    bh, n, chunk, dk = w.shape
    dv = vp.shape[-1]
    step = _chunks_a_step(n)
    last = n // step - 1

    def blocked(rows, width):
        return pl.BlockSpec((None, step, rows, width),
                            lambda b, i: (b, last - i, 0, 0))

    rows = pl.BlockSpec((None, n, dv), lambda b, i: (b, 0, 0))
    du, dw, dkd, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, step=step),
        grid=(bh, n // step),
        in_specs=[blocked(chunk, dk), blocked(chunk, dk), rows,
                  blocked(dk, dv), blocked(chunk, dv), blocked(chunk, dv),
                  blocked(dk, dv)],
        out_specs=[blocked(chunk, dv), blocked(chunk, dk),
                   blocked(chunk, dk), rows],
        out_shape=[jax.ShapeDtypeStruct(vp.shape, vp.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype),
                   jax.ShapeDtypeStruct(kd.shape, kd.dtype),
                   jax.ShapeDtypeStruct((bh, n, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gated_delta_bwd",
    )(w, kd, jnp.broadcast_to(c[..., None], (bh, n, dv)), states, vp,
      dvp, dstates.astype(jnp.float32))
    return dw, du, dkd, jnp.sum(dc, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _state_pass_kernels(w, u, kd, c, interpret):
    return tuple(_kernel_forward(w, u, kd, c, interpret))


def _state_pass_fwd(w, u, kd, c, interpret):
    states, vp = _kernel_forward(w, u, kd, c, interpret)
    return (states, vp), (w, kd, c, states, vp)


def _state_pass_bwd(interpret, res, cotangent):
    return _kernel_backward(*res, *cotangent, interpret)


_state_pass_kernels.defvjp(_state_pass_fwd, _state_pass_bwd)


def kernels_by_default() -> bool:
    """Whether ``gated_delta_rule`` runs the state pass as the two
    kernels where the call does not say (``use_pallas=None``): on a TPU.
    Elsewhere it is the scan, without a word: a caller that needs the
    kernels asks here."""
    return jax.default_backend() == "tpu"


def _chunks(x, chunk):
    """[B, L, H, ...] -> [B, H, L // chunk, chunk, ...]."""
    b, length, h = x.shape[:3]
    x = x.reshape(b, length // chunk, chunk, h, *x.shape[3:])
    return jnp.moveaxis(x, 3, 1)


@functools.partial(jax.jit, static_argnames=("chunk", "use_pallas",
                                             "interpret", "with_state"))
def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                     beta: jax.Array, chunk: int = CHUNK,
                     use_pallas: bool | None = None, interpret: bool = False,
                     with_state: bool = False):
    """q, k [B, L, H, Dk] (as they enter the rule: normalised and scaled
    by the caller), v [B, L, H, Dv], g [B, L, H] float32 log-decays
    (<= 0), beta [B, L, H] float32 write strengths -> o [B, L, H, Dv] in
    ``v``'s dtype; L a multiple of ``chunk``.  A row is one sequence:
    the state starts at nought at position 0 and crosses whatever the
    row holds.  ``use_pallas`` None: the kernels on a TPU, the scan
    elsewhere; ``interpret`` runs them in the Pallas interpreter (CPU
    tests).  ``with_state``: also the state entering each row's last
    chunk, [B, H, Dk, Dv] float32 (a counter's: no gradient)."""
    b, length, h, dk = q.shape
    dv = v.shape[-1]
    if length % chunk:
        raise ValueError(f"row of {length} positions in chunks of {chunk}; "
                         "pad upstream")
    if use_pallas is None:
        use_pallas = kernels_by_default()
    f32, dt = jnp.float32, v.dtype
    n = length // chunk
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

    flat = [x.reshape(b * h, *x.shape[2:]) for x in (w, u, kd, c)]
    if use_pallas:
        states, vp = _state_pass_kernels(*flat, interpret)
    else:
        states, vp = _state_pass_scan(*flat)
    states = states.reshape(b, h, n, dk, dv)
    vp = vp.reshape(b, h, n, chunk, dv)

    qk = jnp.einsum("...ik,...jk->...ij", q, k, preferred_element_type=f32)
    o = jnp.einsum("...ij,...jv->...iv", (qk * among).astype(dt), vp,
                   preferred_element_type=f32) \
        + jnp.einsum("...ik,...kv->...iv", (q * gamma[..., None]).astype(dt),
                     states.astype(dt), preferred_element_type=f32)
    o = jnp.moveaxis(o.astype(dt), 1, 3).reshape(b, length, h, dv)
    if with_state:
        return o, jax.lax.stop_gradient(states[:, :, -1])
    return o
