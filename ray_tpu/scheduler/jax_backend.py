"""TPU-resident batched scheduling kernel — the north star.

Replaces the reference's single-task greedy loop
(``HybridSchedulingPolicy::Schedule`` iterated per task,
``cluster_task_manager.cc:67-123``) with one batched solve per tick:

    demand[C, R] x counts[C] x avail[N, R] -> alloc[C, N]

where C is the number of *scheduling classes* (tasks deduped by interned
resource shape, ``task_spec.h:297`` — 1M pending tasks collapse to ~100s of
rows, SURVEY.md §3.4) and N the number of nodes.

Node ordering is **bucketized**: instead of a total order by exact score
(a 10k-element sort per class — 256 sequential sorts per tick), nodes are
binned into 35 priority buckets and filled in (bucket, rotated-node-id)
order:

    buckets 0-15  — cost pre-buckets: nodes a negative (preferred)
                    per-(class, node) cost pulls ahead of the pack zone
    bucket 16     — below the spread threshold (hybrid policy truncation,
                    ``hybrid_scheduling_policy.cc:100-133``)
    buckets 17-32 — critical-resource utilization quantized to 1/16
    bucket 33     — accelerator nodes avoided by non-accelerator classes
                    (``scheduler_avoid_gpu_nodes`` parity)
    bucket 34     — empty/dead/padded nodes

Within a bucket the fill order is node id **rotated by a per-class
stride** (class c starts at node ``(c * 977) % N_pad``), so concurrent
classes don't all pile onto low-id nodes.  NOTE this is a documented
divergence from the reference's strict min-utilization pick
(``hybrid_scheduling_policy.cc:114-133``): within one 1/16 utilization
bucket the reference would still order by exact score; here ties at
bucket granularity fill round-robin-by-class instead — oracle-matched and
validated against exact vectors before commit wherever it is consumed
(``ClusterTaskManager._schedule_batched``, autoscaler bin-pack).

This mirrors the reference's real semantics (it picks among a top-k
candidate set, not a strict total order) and makes the per-class step
sort-free: prefix capacities come from a two-level blocked cumsum
(groups of 128 nodes), all dense vector ops that XLA maps onto the TPU's
VPU.  The fill is still exact water-filling — capacity-consistent within
the tick because the scan over classes carries the availability matrix.

Two levels of TPU-residency, one solver (the deterministic bucketized
fill, golden-tested against a numpy oracle with identical semantics):
  * ``BatchSolver.solve_matrices`` uploads the caller's [N, R] world
    with every call and fetches the dense alloc[C, N] — the autoscaler's
    pack-mode solve (Serve's kernel placement goes through it too), and
    the single-device reference of ``sharded_solve``.
    ``BatchSolver.solve_bundles`` is the same shape of call for one
    placement group's bundles.
  * ``DeviceRuntimeSolver`` is the **runtime dispatch path**: a raylet's
    ``ClusterTaskManager`` keeps the cluster world state device-resident
    between scheduling ticks, shipping only dirty-row deltas (nodes whose
    availability changed) and the [C] counts vector down, and one packed
    sparse assignment with on-device validation bits back per tick.

The raylet stays authoritative: kernel output is validated against the
exact fixed-point vectors before commit and falls back to the native
policy (``ClusterTaskManager._schedule_greedy``) — dirty/stale views are
tolerated exactly like spillback.
"""

from __future__ import annotations

import functools
import logging
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu._private.config import get_config
from ray_tpu._private.device_policy import enable_compile_cache
from ray_tpu.scheduler.resources import accelerator_node_mask

logger = logging.getLogger(__name__)

_BIG = 1e9
_UTIL_LEVELS = 16
# Cost pre-buckets BELOW the utilization mapping: the per-(class, node)
# cost term moves a node by floor(cost * scale + 1/2) buckets, and a
# negative (preferred) cost needs somewhere to land even when the whole
# fleet sits in the flat below-threshold bucket — without these the
# cost would saturate against bucket 0 and locality/heterogeneity
# preferences would be invisible on an idle cluster.
_COST_BUCKETS = 16
# 16 pre-buckets + [flat below-threshold, 16 util levels, accel-avoid,
# empty] = 35.
_NUM_BUCKETS = _COST_BUCKETS + _UTIL_LEVELS + 3
_GROUP = 128  # node-axis block for the two-level prefix (lane width)
_ROT_STRIDE = 977  # per-class rotation stride (prime, coprime with N_pad)

# Node labels feeding the heterogeneity cost term (Gavel-style
# effective-rate scaling, PAPERS.md 2008.09213): a float throughput
# multiplier per node, with an optional accelerator-class override so
# the rate matrix is genuinely per-class x per-node.  Unlabeled nodes
# rate 1.0; all-equal rates produce a zero cost term (no behavior
# change).
NODE_THROUGHPUT_LABEL = "ray_tpu.throughput"
NODE_ACCEL_THROUGHPUT_LABEL = "ray_tpu.accel_throughput"


def _label_rate(labels: Dict, key: str, default: float = 1.0) -> float:
    try:
        return max(float(labels.get(key, default)), 1e-3)
    except (TypeError, ValueError):
        return default


def _pad_to(x: np.ndarray, shape: Tuple[int, ...]) -> np.ndarray:
    pads = [(0, s - d) for s, d in zip(shape, x.shape)]
    return np.pad(x, pads)


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Shared per-class fill (device).
# ---------------------------------------------------------------------------

def _bucket_fill_step(av, total, d, cnt, is_accel, shift, cost_row, invert,
                      accel_node, empty, spread_threshold):
    """One class's water-fill against the running availability.

    Layout is TPU-native: av/total are [R, N] (resources on the 8-wide
    sublane axis, nodes on the 128-wide lane axis — N is padded to a
    multiple of 128 so every op is tile-aligned) and bucket tensors are
    [B, N] for the same reason.  ``shift`` rotates the within-bucket fill
    order (see module docstring).  Returns (new_av[R,N], take[N]).

    Cost-matrix extension (the unified-scheduler surface): ``cost_row``
    [N] is this class's per-node cost added to the utilization score
    BEFORE bucketization — negative cost pulls a node into an earlier
    fill bucket.  In utilization units: 1/16 per bucket.  Carries the
    heterogeneity term (Gavel-style effective-rate: slower nodes cost
    more), the arg-locality bonus (nodes holding a class's argument
    bytes cost less) and the PG-PACK used-node bonus.  ``invert`` flips
    the utilization ordering (score := 1 - util): most-utilized
    feasible nodes fill first — bin-packing/PACK mode for the
    autoscaler's node-count solve (with zero per-class shifts the
    within-bucket order is plain node id, i.e. first-fit).

    All f32; prefix sums stay exact for integer capacities while the
    running prefix is < 2^24, beyond which the prefix already dwarfs any
    class count so take clamps to 0.
    """
    import jax
    import jax.numpy as jnp

    eps = 1e-6
    n_pad = av.shape[1]
    demanded = d > 0                                       # [R]
    any_demand = jnp.any(demanded)
    # How many tasks of this class fit on each node.
    ratios = jnp.where(demanded[:, None],
                       av / jnp.maximum(d[:, None], eps), _BIG)
    cap = jnp.floor(jnp.min(ratios, axis=0) + eps)         # [N]
    cap = jnp.clip(cap, 0.0, cnt)
    # Hybrid score: current critical-resource utilization over the
    # demanded resources (hybrid_scheduling_policy.cc:100-133).
    util = jnp.where(total > 0, (total - av) / jnp.maximum(total, eps), 0.0)
    score_demanded = jnp.max(
        jnp.where(demanded[:, None], util, -_BIG), axis=0)
    score_overall = jnp.max(util, axis=0)
    score = jnp.where(any_demand, score_demanded, score_overall)  # [N]
    score = jnp.where(invert > 0, 1.0 - score, score)
    # Bucketize: below threshold -> flat pack zone; else utilization
    # quantized — then offset by the cost term in BUCKET units (with
    # 16 pre-buckets below the pack zone so preferences resolve even
    # when the whole fleet ties at bucket 0).  cost == 0 shifts
    # uniformly by _COST_BUCKETS: identical fill order to the cost-free
    # kernel.
    scale = _UTIL_LEVELS / jnp.maximum(1.0 - spread_threshold, eps)
    lvl = jnp.clip(
        jnp.floor((score - spread_threshold) * scale) + 1.0,
        1.0, float(_UTIL_LEVELS))
    b_util = jnp.where(score < spread_threshold, 0.0, lvl)
    cost_b = jnp.floor(cost_row * scale + 0.5)
    bucket = jnp.clip(b_util + float(_COST_BUCKETS) + cost_b,
                      0.0, float(_COST_BUCKETS + _UTIL_LEVELS))
    bucket = jnp.where(jnp.logical_and(accel_node, ~is_accel),
                       float(_COST_BUCKETS + _UTIL_LEVELS + 1), bucket)
    bucket = jnp.where(empty, float(_NUM_BUCKETS - 1), bucket)
    bucket = bucket.astype(jnp.int32)
    # Prefix capacity in (bucket, rotated node-id) order — sort-free,
    # [B, N], and roll-free: instead of materializing the rolled tensor
    # (two full [B, N] memory passes), compute the NATURAL-order
    # per-bucket exclusive prefix P and decompose the rotation
    # analytically.  With Q[b] = P[b, shift] (capacity in bucket b
    # before the rotation start) and S[b] the bucket total, a node n's
    # within-bucket prefix in rotated order is
    #     n >= shift:  P[b, n] - Q[b]          (nodes [shift, n))
    #     n <  shift:  S[b] - Q[b] + P[b, n]   (wrap: [shift, N) + [0, n))
    onehot = (bucket[None, :] ==
              jnp.arange(_NUM_BUCKETS, dtype=jnp.int32)[:, None])
    cap_oh = jnp.where(onehot, cap[None, :], 0.0)          # [B, N]
    g = cap_oh.reshape(_NUM_BUCKETS, n_pad // _GROUP, _GROUP)
    gsum = jnp.sum(g, axis=2)                              # [B, G]
    gprefix = jnp.cumsum(gsum, axis=1) - gsum              # excl. over groups
    # Within-group exclusive prefix as ONE strictly-lower-triangular
    # matmul on the MXU (f32-exact below 2^24) instead of log2(128)
    # VPU shift passes over the [B, N] tensor.
    tri = jnp.triu(jnp.ones((_GROUP, _GROUP), jnp.float32), k=1)
    within = jax.lax.dot_general(
        g, tri, (((2,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)               # [B, G, GROUP]
    p_nat = (within + gprefix[:, :, None]).reshape(_NUM_BUCKETS, n_pad)
    btotal = jnp.sum(gsum, axis=1)                         # [B]  (= S)
    q_at_shift = jax.lax.dynamic_slice_in_dim(
        p_nat, shift, 1, axis=1)[:, 0]                     # [B]  (= Q)
    bprefix = jnp.cumsum(btotal) - btotal                  # excl. over buckets
    wrap = jnp.where(jnp.arange(n_pad) < shift,
                     btotal[:, None], 0.0)                 # [B, N]
    prefix_bn = p_nat - q_at_shift[:, None] + wrap + bprefix[:, None]
    # Select each node's own-bucket entry (masked sum avoids a gather).
    prefix = jnp.sum(jnp.where(onehot, prefix_bn, 0.0), axis=0)
    take = jnp.clip(cnt - prefix, 0.0, cap)
    av = av - take[None, :] * d[:, None]
    return av, take


def _class_shifts(c_pad: int, n_pad: int):
    """Per-class within-bucket rotation offsets (device)."""
    import jax.numpy as jnp
    return (jnp.arange(c_pad, dtype=jnp.int32) * _ROT_STRIDE) % n_pad


def _pallas_enabled() -> bool:
    """Fuse the per-class fill into one Mosaic kernel?  On the TPU, yes
    (tests run the jnp path on CPU; equivalence is covered by an
    interpret-mode test and, on the chip, by chip_smoke.py).  A Mosaic
    compile or run error propagates to the caller — there is no
    run-time switch to the jnp scan."""
    import jax
    return jax.default_backend() == "tpu"


def _fill_name(use_pallas: bool) -> str:
    return "pallas" if use_pallas else "jnp"


@functools.lru_cache(maxsize=16)
def _pallas_class_fill(c_pad: int, n_pad: int, r_pad: int,
                       interpret: bool = False):
    """The whole class scan as ONE Mosaic kernel: grid over classes,
    availability carried in VMEM scratch across grid steps.

    The jnp path lowers each class step to ~10 fused XLA kernels; at
    256 classes that is ~2,500 sequential kernel launches a tick whose
    fixed overheads dominate it (the arrays are far too small to be
    bandwidth-bound).  Here one kernel invocation per class
    does everything in VMEM — the [B, N] bucket tensors never touch
    HBM, and per-class HBM traffic is one [1, N] allocs row out.

    Same math as ``_bucket_fill_step`` with two kernel-friendly
    substitutions (both f32-exact for integer capacities < 2^24):
      * the within-bucket exclusive prefix is a lane-axis Hillis-Steele
        scan (``pltpu.roll`` + iota mask) instead of the blocked
        reshape/cumsum;
      * the bucket-prefix cumsum over B=35 entries is a strictly-lower
        triangular matmul at Precision.HIGHEST (MXU bf16 passes round
        integers like 265 — HIGHEST is required for exactness).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B = _NUM_BUCKETS
    eps = 1e-6

    def kernel(counts_ref, accel_ref, shifts_ref, thr_ref,
               demand_ref, total_ref, accel_node_ref, av0_ref, cost_ref,
               av_out_ref, allocs_ref, av_s):
        c = pl.program_id(0)

        @pl.when(c == 0)
        def _init():
            av_s[...] = av0_ref[...]

        av = av_s[...]                                     # [R, N]
        total = total_ref[...]                             # [R, N]
        cnt = counts_ref[c]
        is_accel = accel_ref[c] > 0
        shift = shifts_ref[c]
        thr = thr_ref[0]
        inv = thr_ref[1]
        d = demand_ref[0]                                  # [R, 1]
        cost = cost_ref[0]                                 # [1, N]
        demanded = d > 0
        any_demand = jnp.any(demanded)
        ratios = jnp.where(demanded, av / jnp.maximum(d, eps), _BIG)
        cap = jnp.floor(jnp.min(ratios, axis=0, keepdims=True) + eps)
        cap = jnp.clip(cap, 0.0, cnt)                      # [1, N]
        util = jnp.where(total > 0,
                         (total - av) / jnp.maximum(total, eps), 0.0)
        score_d = jnp.max(jnp.where(demanded, util, -_BIG),
                          axis=0, keepdims=True)
        score_o = jnp.max(util, axis=0, keepdims=True)
        score = jnp.where(any_demand, score_d, score_o)    # [1, N]
        score = jnp.where(inv > 0, 1.0 - score, score)
        empty = jnp.max(total, axis=0, keepdims=True) <= 0.0
        accel_node = accel_node_ref[...] > 0.0             # [1, N]
        scale = _UTIL_LEVELS / jnp.maximum(1.0 - thr, eps)
        lvl = jnp.clip(jnp.floor((score - thr) * scale) + 1.0,
                       1.0, float(_UTIL_LEVELS))
        b_util = jnp.where(score < thr, 0.0, lvl)
        cost_b = jnp.floor(cost * scale + 0.5)
        bucket = jnp.clip(b_util + float(_COST_BUCKETS) + cost_b,
                          0.0, float(_COST_BUCKETS + _UTIL_LEVELS))
        bucket = jnp.where(
            jnp.logical_and(accel_node, jnp.logical_not(is_accel)),
            float(_COST_BUCKETS + _UTIL_LEVELS + 1), bucket)
        bucket = jnp.where(empty, float(B - 1), bucket).astype(jnp.int32)
        onehot = bucket == jax.lax.broadcasted_iota(
            jnp.int32, (B, n_pad), 0)
        cap_oh = jnp.where(onehot, cap, 0.0)               # [B, N]
        lane = jax.lax.broadcasted_iota(jnp.int32, (B, n_pad), 1)
        p = cap_oh
        k = 1
        while k < n_pad:
            p = p + jnp.where(lane >= k, pltpu.roll(p, k, 1), 0.0)
            k *= 2
        p_nat = p - cap_oh                                 # excl. prefix
        btotal = jnp.max(p, axis=1, keepdims=True)         # [B, 1]
        before = lane < shift
        q = jnp.sum(jnp.where(before, cap_oh, 0.0),
                    axis=1, keepdims=True)                 # [B, 1]
        row = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
        tri_excl = (col < row).astype(jnp.float32)
        bprefix = jax.lax.dot_general(
            tri_excl, btotal, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)            # [B, 1]
        # Rotation decomposed analytically (see _bucket_fill_step).
        prefix_bn = p_nat - q + jnp.where(before, btotal, 0.0) + bprefix
        prefix = jnp.sum(jnp.where(onehot, prefix_bn, 0.0),
                         axis=0, keepdims=True)            # [1, N]
        take = jnp.clip(cnt - prefix, 0.0, cap)
        av_s[...] = av - d * take
        allocs_ref[...] = take[None]

        @pl.when(c == c_pad - 1)
        def _fin():
            av_out_ref[...] = av_s[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(c_pad,),
        in_specs=[
            pl.BlockSpec((1, r_pad, 1), lambda c, *_: (c, 0, 0)),
            pl.BlockSpec((r_pad, n_pad), lambda c, *_: (0, 0)),
            pl.BlockSpec((1, n_pad), lambda c, *_: (0, 0)),
            pl.BlockSpec((r_pad, n_pad), lambda c, *_: (0, 0)),
            pl.BlockSpec((1, 1, n_pad), lambda c, *_: (c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((r_pad, n_pad), lambda c, *_: (0, 0)),
            pl.BlockSpec((1, 1, n_pad), lambda c, *_: (c, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((r_pad, n_pad), jnp.float32)],
    )
    fn = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r_pad, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((c_pad, 1, n_pad), jnp.float32),
        ],
        interpret=interpret,
        # The kernel's event in a device trace (else the enclosing
        # function's name, ``solve``, which every program here shares).
        name="scheduler_class_fill",
    )

    def fill(av_t, total_t, demand, counts, accel_class, accel_node,
             spread_threshold, cost, invert, shifts):
        import jax.numpy as jnp
        av_out, allocs = fn(
            counts.astype(jnp.float32),
            accel_class.astype(jnp.int32),
            shifts.astype(jnp.int32),
            jnp.stack([jnp.asarray(spread_threshold, jnp.float32),
                       jnp.asarray(invert, jnp.float32)]),
            demand[:, :, None].astype(jnp.float32),
            total_t,
            accel_node.astype(jnp.float32)[None, :],
            av_t,
            cost[:, None, :].astype(jnp.float32))
        return av_out, allocs[:, 0, :]

    return fill


def _class_fill(av_t, total_t, demand, counts, accel_class, accel_node,
                spread_threshold, *, c_pad: int, n_pad: int, r_pad: int,
                use_pallas: bool, cost=None, invert=None, shifts=None):
    """Run the per-class waterfill over all classes against ``av_t``.

    ``cost`` [C, N] per-(class, node) score offsets (None = zeros),
    ``invert`` scalar flag for pack mode, ``shifts`` [C] within-bucket
    rotation offsets (None = the default per-class stride).  Returns
    (av_after [R, N], allocs [C, N]).  ``use_pallas`` selects the fused
    Mosaic kernel (``_pallas_enabled``: on TPU) over the jnp scan, both
    oracle-exact; asked for off the chip, the kernel runs in the Pallas
    interpreter so tests and chip_smoke.py's CPU drive reach it."""
    import jax
    import jax.numpy as jnp

    if cost is None:
        cost = jnp.zeros((c_pad, n_pad), jnp.float32)
    if invert is None:
        invert = jnp.float32(0.0)
    if shifts is None:
        shifts = _class_shifts(c_pad, n_pad)
    if use_pallas:
        fill = _pallas_class_fill(
            c_pad, n_pad, r_pad, interpret=jax.default_backend() != "tpu")
        return fill(av_t, total_t, demand, counts, accel_class,
                    accel_node, spread_threshold, cost, invert, shifts)
    empty = jnp.max(total_t, axis=0) <= 0

    def body(av, inputs):
        d, cnt, is_accel, shift, cost_row = inputs
        return _bucket_fill_step(av, total_t, d, cnt, is_accel, shift,
                                 cost_row, invert, accel_node, empty,
                                 spread_threshold)

    with jax.named_scope("scheduler_class_fill"):
        av_after, allocs = jax.lax.scan(
            body, av_t, (demand, counts, accel_class, shifts, cost),
            unroll=8)
    return av_after, allocs


def _pack_tick(allocs, counts_k, av_pre, demand, nnz_max):
    """On-device validation + fixed-size sparse encoding for one tick.

    Returns (packed[2*nnz_max+3], placed_c[C]).  Sparse indices are exact
    in f32 while C_pad*N_pad < 2^24 (asserted by callers).  Compaction is
    ``jnp.nonzero(size=...)`` — XLA's static-size stream compaction —
    which replaced the earlier rank-cumsum + searchsorted formulation
    (21 binary-search steps of 32k gathers each dominated the tick).
    """
    import jax
    import jax.numpy as jnp

    with jax.named_scope("scheduler_pack_tick"):
        flat_n = allocs.shape[0] * allocs.shape[1]
        usage = jnp.einsum("cn,cr->rn", allocs, demand)
        ok_cap = jnp.all(usage <= av_pre + 1e-2)
        placed_c = jnp.sum(allocs, axis=1)                     # [C]
        ok_cnt = jnp.all(placed_c <= counts_k + 0.5)
        placed = jnp.sum(placed_c)
        flat = allocs.reshape(flat_n)
        nz = flat > 0
        nnz = jnp.sum(nz.astype(jnp.int32))
        (pos,) = jnp.nonzero(nz, size=nnz_max, fill_value=flat_n)
        live = jnp.arange(nnz_max) < nnz
        posc = jnp.minimum(pos, flat_n - 1)
        idx = jnp.where(live, posc, flat_n)
        vals = jnp.where(live, flat[posc], 0.0)
        ok = ok_cap & ok_cnt & (nnz <= nnz_max)
        packed = jnp.concatenate([
            idx.astype(jnp.float32), vals,
            jnp.stack([placed, ok.astype(jnp.float32),
                       nnz.astype(jnp.float32)])])
    return packed, placed_c


def _unpack_tick(packed: np.ndarray, nnz_max: int):
    """Host side of ``_pack_tick``: (idx [nnz_max] int64, vals [nnz_max],
    placed, ok, nnz) of one fetched tick."""
    return (np.rint(packed[:nnz_max]).astype(np.int64),
            packed[nnz_max:2 * nnz_max],
            int(np.rint(packed[2 * nnz_max])),
            bool(packed[2 * nnz_max + 1] > 0.5),
            int(np.rint(packed[2 * nnz_max + 2])))


def _dense_alloc(idx: np.ndarray, vals: np.ndarray, c_pad: int,
                 n_pad: int) -> np.ndarray:
    """Dense alloc[c_pad, n_pad] int64 from a tick's sparse pairs (flat
    index class * n_pad + node; unused slots carry c_pad * n_pad)."""
    live = idx < c_pad * n_pad
    alloc = np.zeros((c_pad, n_pad), dtype=np.int64)
    alloc.reshape(-1)[idx[live]] = np.rint(vals[live]).astype(np.int64)
    return alloc


# ---------------------------------------------------------------------------
# Device kernels (jit-compiled once per padded shape).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def _jit_waterfill(c_pad: int, n_pad: int, r_pad: int,
                   use_pallas: bool = False):
    import jax

    def solve(avail, total, demand, counts, accel_node, accel_class,
              spread_threshold, cost, invert, shifts):
        # avail/total: [N, R]; demand: [C, R]; counts: [C].  Transposed
        # once to the TPU-native [R, N] layout (see _bucket_fill_step).
        final_avail, allocs = _class_fill(
            avail.T, total.T, demand, counts, accel_class, accel_node,
            spread_threshold, c_pad=c_pad, n_pad=n_pad, r_pad=r_pad,
            use_pallas=use_pallas, cost=cost, invert=invert,
            shifts=shifts)
        return allocs, final_avail.T

    return jax.jit(solve)


@functools.lru_cache(maxsize=16)
def _jit_solve_tick(c_pad: int, n_pad: int, r_pad: int, nnz_max: int,
                    use_pallas: bool = False):
    """One runtime scheduling tick against DEVICE-RESIDENT world state.

    Unlike ``_jit_waterfill`` this takes the transposed [R, N] matrices a
    ``DeviceRuntimeSolver`` keeps on device between ticks — only the [C]
    counts vector crosses host->device, only the packed sparse assignment
    comes back (``_pack_tick``'s validation bits included).
    """
    import jax
    import jax.numpy as jnp

    assert c_pad * n_pad < (1 << 24), "sparse idx must stay exact in f32"

    def solve(avail_t, total_t, demand, counts, accel_node, accel_class,
              spread_threshold, cost):
        _, allocs = _class_fill(
            avail_t, total_t, demand, counts, accel_class, accel_node,
            spread_threshold, c_pad=c_pad, n_pad=n_pad, r_pad=r_pad,
            use_pallas=use_pallas, cost=cost)
        packed, _ = _pack_tick(allocs, counts, avail_t, demand, nnz_max)
        return packed

    return jax.jit(solve)


@functools.lru_cache(maxsize=16)
def _jit_apply_rows(n_pad: int, r_pad: int, k_pad: int):
    """Scatter k dirty node rows into the device-resident avail matrix."""
    import jax

    def apply(avail_t, idx, rows):
        # avail_t [R, N]; idx [k]; rows [k, R].  Padding duplicates the
        # last real entry, so duplicate-index writes carry equal values.
        return avail_t.at[:, idx].set(rows.T)

    return jax.jit(apply, donate_argnums=(0,))


@functools.lru_cache(maxsize=16)
def _jit_pack_bundles(b_pad: int, n_pad: int, r_pad: int):
    """Placement-group bundle -> node solve as ONE device program.

    Bundles are count-1 demands whose placement interacts through the
    evolving (availability, used-node) carry, so the solve is a scan
    over bundle rows — still a single dispatch for the whole group
    (and the strategy semantics live in the cost, not host loops):

      * score = LeastResourceScorer best-fit (after-allocation leftover
        of the demanded resources, gcs_resource_scheduler.h:74),
      * PACK  -> ``pack_w`` > 0 bonus on already-used nodes,
        SPREAD -> ``pack_w`` < 0 penalty (soft constraints),
      * STRICT_SPREAD -> used nodes masked infeasible (hard),
      * STRICT_PACK is collapsed by the host into one composite row.

    Returns (node_idx [B] int32, ok [B] bool).  Padded bundle rows
    (empty demand) are no-ops; padded nodes (zero total) are never
    feasible.  The host validates the assignment against the exact
    quantized vectors before the 2PC prepare — kernel output never
    commits unchecked (same contract as the task tick).
    """
    import jax
    import jax.numpy as jnp

    def solve(avail, total, demand, excluded, used0, pack_w,
              strict_spread):
        eps = 1e-6
        alive = jnp.max(total, axis=1) > 0                 # [N]
        node_ok = alive & ~excluded

        def body(carry, d):
            av, used = carry
            demanded = d > 0                               # [R]
            is_real = jnp.any(demanded)
            feasible = jnp.all(av + eps >= d[None, :], axis=1) & node_ok
            feasible = jnp.where(strict_spread > 0,
                                 feasible & ~used, feasible)
            # LeastResourceScorer: mean over demanded resources of
            # 1 - leftover/have — higher = tighter fit (best fit).
            terms = jnp.where(
                demanded[None, :],
                1.0 - (av - d[None, :]) / jnp.maximum(av, 1.0), 0.0)
            nd = jnp.maximum(jnp.sum(demanded.astype(jnp.float32)), 1.0)
            sc = jnp.sum(terms, axis=1) / nd
            sc = sc + pack_w * used.astype(jnp.float32)
            sc = jnp.where(feasible, sc, -_BIG)
            best = jnp.argmax(sc).astype(jnp.int32)
            ok = is_real & (sc[best] > -_BIG / 2)
            hot = (jnp.arange(av.shape[0]) == best) & ok   # [N]
            av = av - jnp.where(hot[:, None], d[None, :], 0.0)
            used = used | hot
            return (av, used), (best, ok)

        (_, _), (idx, ok) = jax.lax.scan(body, (avail, used0), demand)
        return idx, ok

    return jax.jit(solve)


# ---------------------------------------------------------------------------
# numpy oracle (golden reference for tests).
# ---------------------------------------------------------------------------

def bucket_oracle(score: np.ndarray, accel_avoid: np.ndarray,
                  empty: np.ndarray, spread_threshold: float,
                  cost: Optional[np.ndarray] = None) -> np.ndarray:
    """Quantize scores into fill-priority buckets (same spec as device):
    the utilization mapping (flat pack zone below the threshold, 16
    quantized levels above) offset by the cost term in bucket units,
    with 16 pre-buckets below the pack zone for cost-preferred nodes."""
    thr = np.float32(spread_threshold)
    scale = np.float32(_UTIL_LEVELS) / max(np.float32(1.0) - thr,
                                           np.float32(1e-6))
    lvl = np.clip(np.floor((score - thr) * scale) + 1.0, 1.0, _UTIL_LEVELS)
    b_util = np.where(score < thr, np.float32(0.0), lvl)
    if cost is None:
        cost_b = np.float32(0.0)
    else:
        cost_b = np.floor(cost.astype(np.float32) * scale +
                          np.float32(0.5))
    bucket = np.clip(b_util + np.float32(_COST_BUCKETS) + cost_b,
                     0.0, _COST_BUCKETS + _UTIL_LEVELS)
    bucket = np.where(accel_avoid, _COST_BUCKETS + _UTIL_LEVELS + 1,
                      bucket)
    bucket = np.where(empty, _NUM_BUCKETS - 1, bucket)
    return bucket.astype(np.int32)


def waterfill_oracle(avail: np.ndarray, total: np.ndarray,
                     demand: np.ndarray, counts: np.ndarray,
                     accel_node: np.ndarray, accel_class: np.ndarray,
                     spread_threshold: float,
                     cost: Optional[np.ndarray] = None,
                     invert_util: bool = False,
                     zero_shifts: bool = False,
                     n_pad: Optional[int] = None) -> np.ndarray:
    """Pure-numpy reference of the bucketized waterfill (same semantics,
    including the per-class within-bucket rotation, the per-(class,node)
    ``cost`` offsets and the inverted-utilization pack mode).

    Float32 throughout so score/bucket boundaries match the device kernel
    bit-for-bit.  ``n_pad`` overrides the padded ring width the rotation
    wraps on — the sharded solve pads to ``_GROUP * n_shards`` instead of
    ``_GROUP``, so parity tests pass the sharded ring explicitly to pin
    bit-exactness at non-aligned ``N``."""
    avail = avail.astype(np.float32).copy()
    total = total.astype(np.float32)
    C, R = demand.shape
    N = avail.shape[0]
    if n_pad is None:
        n_pad = _round_up(max(N, 8), _GROUP)
    alloc = np.zeros((C, N), dtype=np.int64)
    eps = np.float32(1e-6)
    empty = total.max(axis=1) <= 0
    node_ids = np.arange(N)
    for c in range(C):
        d = demand[c].astype(np.float32)
        cnt = int(counts[c])
        if cnt == 0:
            continue
        demanded = d > 0
        if demanded.any():
            ratios = np.where(demanded[None, :],
                              avail / np.maximum(d[None, :], eps), _BIG)
            cap = np.floor(ratios.min(axis=1) + eps)
        else:
            cap = np.full(N, _BIG, dtype=np.float32)
        cap = np.clip(cap, 0, cnt).astype(np.int64)
        util = np.where(total > 0, (total - avail) / np.maximum(total, eps),
                        np.float32(0.0)).astype(np.float32)
        if demanded.any():
            score = np.where(demanded[None, :], util,
                             np.float32(-_BIG)).max(axis=1)
        else:
            score = util.max(axis=1)
        score = score.astype(np.float32)
        if invert_util:
            score = (np.float32(1.0) - score).astype(np.float32)
        accel_avoid = accel_node & (not accel_class[c])
        bucket = bucket_oracle(score.astype(np.float32), accel_avoid, empty,
                               spread_threshold,
                               cost=None if cost is None else cost[c])
        # Fill order: (bucket, node-id rotated by the class stride) — the
        # padded nodes carry zero capacity so only the real nodes'
        # relative rolled order matters.
        shift = 0 if zero_shifts else (c * _ROT_STRIDE) % n_pad
        rot_key = (node_ids - shift) % n_pad
        order = np.lexsort((rot_key, bucket))
        remaining = cnt
        for n in order:
            if remaining <= 0:
                break
            take = min(remaining, int(cap[n]))
            if take > 0:
                alloc[c, n] = take
                avail[n] -= take * d
                remaining -= take
    return alloc


# ---------------------------------------------------------------------------
# Host-side driver.
# ---------------------------------------------------------------------------

class BatchSolver:
    """One dense solve per call over matrices the caller already holds
    (the autoscaler's bin-packing, placement-group bundles): pads,
    picks the single-device or the node-sharded program, returns host
    arrays."""

    def __init__(self):
        enable_compile_cache()
        #: Which program the LAST solve ran: "single/pallas",
        #: "single/jnp" or "sharded[n]/jnp" (chip_smoke.py prints it).
        self.last_path: Optional[str] = None

    # -- raw matrix interface (the autoscaler's pack-mode solve) ---------
    def solve_matrices(self, avail: np.ndarray, total: np.ndarray,
                       demand: np.ndarray, counts: np.ndarray,
                       accel_node: Optional[np.ndarray] = None,
                       accel_class: Optional[np.ndarray] = None,
                       spread_threshold: Optional[float] = None,
                       cost: Optional[np.ndarray] = None,
                       invert_util: bool = False,
                       zero_shifts: bool = False):
        """Returns alloc[C,N] int64 for one tick.

        ``cost`` [C, N] adds per-(class, node) score offsets (negative =
        preferred); ``invert_util`` + ``zero_shifts`` select pack mode
        (most-utilized-first, first-fit within a bucket) — the
        autoscaler's node-count bin-packing ordering.

        Above the ``solver_shard_min_nodes`` gate (and with >1 device
        visible) the solve runs node-sharded across the local mesh
        (``sharded_solve``).  A device or compile error on either path
        propagates."""
        import jax
        C, R = demand.shape
        N = avail.shape[0]
        if accel_node is None:
            accel_node = np.zeros(N, dtype=bool)
        if accel_class is None:
            accel_class = np.zeros(C, dtype=bool)
        if spread_threshold is None:
            spread_threshold = get_config().scheduler_spread_threshold
        from ray_tpu.scheduler import sharded_solve
        n_shards = sharded_solve.plan_shards(N)
        if n_shards > 1:
            self.last_path = f"sharded[{n_shards}]/jnp"
            return sharded_solve.solve_matrices_sharded(
                avail, total, demand, counts, accel_node,
                accel_class, spread_threshold, cost, invert_util,
                zero_shifts, n_shards)
        c_pad, n_pad, r_pad = self._pads(C, N, R)
        cost_p = np.zeros((c_pad, n_pad), np.float32) if cost is None \
            else _pad_to(cost.astype(np.float32), (c_pad, n_pad))
        shifts = np.zeros(c_pad, np.int32) if zero_shifts else \
            np.asarray((np.arange(c_pad) * _ROT_STRIDE) % n_pad,
                       np.int32)
        use_pallas = _pallas_enabled()
        self.last_path = f"single/{_fill_name(use_pallas)}"
        allocs, _ = _jit_waterfill(c_pad, n_pad, r_pad, use_pallas)(
            _pad_to(avail.astype(np.float32), (n_pad, r_pad)),
            _pad_to(total.astype(np.float32), (n_pad, r_pad)),
            _pad_to(demand.astype(np.float32), (c_pad, r_pad)),
            _pad_to(counts.astype(np.float32), (c_pad,)),
            _pad_to(accel_node.astype(bool), (n_pad,)),
            _pad_to(accel_class.astype(bool), (c_pad,)),
            np.float32(spread_threshold), cost_p,
            np.float32(1.0 if invert_util else 0.0), shifts)
        allocs = np.asarray(jax.device_get(allocs))[:C, :N]
        return np.rint(allocs).astype(np.int64)

    # -- bundle interface (GCS placement groups) -------------------------
    def solve_bundles(self, avail: np.ndarray, total: np.ndarray,
                      demand: np.ndarray, strategy: str,
                      excluded: Optional[np.ndarray] = None):
        """Bundle -> node indices for one placement group in one device
        call (``_jit_pack_bundles``).  ``demand`` is [B, R] in host
        (unsorted) order; strategy semantics ride the kernel's cost and
        masks.  Returns (node_idx [B] int64, ok [B] bool) — callers
        treat any ``~ok`` as all-or-nothing failure and re-validate
        against exact vectors before committing.

        Sharded above the ``solver_shard_min_nodes`` gate: the
        cross-shard argmax keeps the exact first-max tie-break, so the
        sharded solve is bit-identical for any N (see sharded_solve)."""
        import jax
        B, R = demand.shape
        N = avail.shape[0]
        from ray_tpu.scheduler import sharded_solve
        n_shards = sharded_solve.plan_shards(N)
        if n_shards > 1:
            return sharded_solve.solve_bundles_sharded(
                avail, total, demand, strategy, excluded, n_shards)
        b_pad = _round_up(max(B, 1), 8)
        n_pad = _round_up(max(N, 8), _GROUP)
        r_pad = _round_up(max(R, 1), 8)
        if excluded is None:
            excluded = np.zeros(N, dtype=bool)
        pack_w = {"PACK": 10.0, "SPREAD": -10.0}.get(strategy, 0.0)
        fn = _jit_pack_bundles(b_pad, n_pad, r_pad)
        idx, ok = fn(
            _pad_to(avail.astype(np.float32), (n_pad, r_pad)),
            _pad_to(total.astype(np.float32), (n_pad, r_pad)),
            _pad_to(demand.astype(np.float32), (b_pad, r_pad)),
            _pad_to(excluded.astype(bool), (n_pad,)),
            np.zeros(n_pad, dtype=bool),
            np.float32(pack_w),
            np.float32(1.0 if strategy == "STRICT_SPREAD" else 0.0))
        idx = np.asarray(jax.device_get(idx))[:B].astype(np.int64)
        ok = np.asarray(jax.device_get(ok))[:B].astype(bool)
        return idx, ok

    @staticmethod
    def _pads(C: int, N: int, R: int) -> Tuple[int, int, int]:
        return (_round_up(max(C, 1), 8), _round_up(max(N, 8), _GROUP),
                _round_up(max(R, 1), 8))


class DeviceRuntimeSolver:
    """Device-resident scheduling session for the RUNTIME dispatch path.

    This is what ``ClusterTaskManager._schedule_batched`` runs
    (``scheduler_backend=jax``, the default): the cluster world state
    lives on device between scheduling ticks —

      * full upload only on structural change (node joined/left, new
        resource column, capacity growth), detected via the view's
        version counter;
      * otherwise only DIRTY node rows (availability changed by local
        grants/releases or usage broadcasts since the last tick) are
        scattered in via ``_jit_apply_rows``;
      * per tick, only the [C] counts vector goes down and one packed
        sparse assignment (with ``_pack_tick``'s on-device validation
        bits) comes back.

    The solver never mutates the device availability with its own
    placements: the host view stays authoritative (``view.subtract`` on
    commit marks rows dirty, which re-syncs them next tick) — stale
    output is validated before commit and falls back exactly like
    spillback.  When the device path yields no valid assignment
    (``ok`` bit false, ``nnz`` overflow, class cap) ``solve`` returns
    None, counts it under ``stats["fallbacks"]`` and the caller runs
    the native greedy path — that is the design.  A device or compile
    ERROR also returns None (the raylet must keep scheduling) but is a
    different thing: it is counted under ``stats["device_errors"]``
    and logged with its traceback the first time, so a broken kernel
    never reads as a stale view.
    """

    _NNZ_BUCKETS = (256, 2048, 16384, 131072)
    # A class row idle this many ticks is an eviction candidate when the
    # demand matrix would otherwise have to grow (growing c_cap
    # recompiles _jit_solve_tick, so eviction is strictly cheaper).
    _CLASS_IDLE_TICKS = 256
    # Hard bound on interned class rows.  Past this the tick falls back
    # to the native greedy path instead of growing without limit — a
    # single tick with >4096 *distinct live* resource shapes is outside
    # the kernel's design envelope anyway.
    _MAX_CLASS_ROWS = 4096

    def __init__(self, node_label: str = "", locality_provider=None):
        self._state: Optional[dict] = None
        # scheduling_class -> demand row.  Rows grow as classes are
        # interned and are compacted by _evict_stale_classes when growth
        # would force a recompile (see _CLASS_IDLE_TICKS).
        self._class_rows: Dict[int, int] = {}
        self._class_reqs: List = []
        self._class_last_used: Dict[int, int] = {}
        self._demand_host: Optional[np.ndarray] = None   # [c_cap, r_pad]
        self._accel_host: Optional[np.ndarray] = None    # [c_cap]
        self._demand_dev = None
        self._accel_dev = None
        self._zero_cost_dev = None                       # [c_cap, n_pad]
        # Callable(list_of_specs) -> Dict[node_id, arg_bytes]: the
        # arg-locality signal (object sizes + locations from the object
        # directory), provided by the owning ClusterTaskManager.  None
        # disables the locality cost term.
        self._locality_provider = locality_provider
        # True when the LAST solve shipped a nonzero cost matrix — the
        # caller uses it to label spillbacks (no_capacity vs
        # locality_override) honestly.
        self.last_cost_active = False
        #: Which program the LAST device tick ran (see BatchSolver).
        self.last_path: Optional[str] = None
        self.stats = {"ticks": 0, "full_syncs": 0, "row_deltas": 0,
                      "fallbacks": 0, "class_evictions": 0,
                      "cost_ticks": 0, "sharded_ticks": 0,
                      "device_errors": 0, "solve_programs": 0}
        self._programs: set = set()   # (c_cap, n_pad, r_pad, nnz, path)
        from ray_tpu._private.metrics_agent import (get_metrics_registry,
                                                    record_internal)
        # Label by owning node: one solver per raylet, and unlabeled
        # series from several solvers would overwrite each other.
        labels = {"node": node_label} if node_label else {}

        def _collect(solver):
            for k, v in solver.stats.items():
                record_internal(f"ray_tpu.scheduler.{k}", v, **labels)
            record_internal("ray_tpu.scheduler.interned_classes",
                            len(solver._class_reqs), **labels)
        get_metrics_registry().register_collector(self, _collect)
        # Probe once: without jax the device path is permanently off —
        # a failed import is NOT cached in sys.modules, so retrying it
        # every scheduling tick would rescan sys.path on the hot path.
        import importlib.util
        self._jax_ok = importlib.util.find_spec("jax") is not None
        if self._jax_ok:
            # The full-width tick takes 10-14 s to compile on the
            # raylet's loop; a placed cache makes that a one-time cost.
            enable_compile_cache()

    # -- public ----------------------------------------------------------
    def solve(self, view, specs: Sequence) -> Optional[List]:
        """Per-spec node targets, or None if the device path could not
        produce a valid assignment (caller must fall back to greedy)."""
        from ray_tpu.scheduler.policy import SchedulingType
        # Reset per call: a tick with no HYBRID groups never reaches
        # _build_cost, and a stale True from the previous tick would
        # mislabel this tick's spillbacks as locality_override.
        self.last_cost_active = False
        groups: Dict[int, List[int]] = {}
        fallback: List[int] = []
        for i, spec in enumerate(specs):
            opts = spec.scheduling_options
            if opts.scheduling_type is SchedulingType.HYBRID:
                groups.setdefault(spec.scheduling_class, []).append(i)
            else:
                fallback.append(i)
        targets: List = [None] * len(specs)
        if groups:
            if not self._jax_ok:
                self.stats["fallbacks"] += 1
                return None
            try:
                if not self._solve_groups(view, specs, groups, targets):
                    self.stats["fallbacks"] += 1
                    return None
            except Exception:
                # Device or compile error (not an invalid assignment).
                # The session may hold a donated-away or half-synced
                # device buffer, and the view's dirty set was already
                # drained: force a full resync next tick.
                self._state = None
                self.stats["device_errors"] += 1
                if self.stats["device_errors"] == 1:
                    logger.exception(
                        "scheduler device solve failed (%s); this tick "
                        "runs greedy — later failures only bump "
                        "stats['device_errors']", self.last_path)
                return None
        if fallback:
            from ray_tpu.scheduler import policy as policy_mod
            for i in fallback:
                targets[i] = policy_mod.schedule(
                    view, specs[i].resources, specs[i].scheduling_options,
                    local_node_id=None)
        return targets

    # -- internals -------------------------------------------------------
    def _solve_groups(self, view, specs, groups, targets) -> bool:
        # The spans below split the tick from inside (children of the
        # raylet's ``scheduler.solve``): a traced window attributes the
        # device's idle gaps to them.  Sharded, ``dispatch`` also holds
        # the wait and the download (``solve_tick_sharded`` returns host
        # arrays) and ``fetch`` is empty.
        from ray_tpu.util import tracing
        self.stats["ticks"] += 1
        with tracing.span("scheduler.solve.sync", category="sched"):
            ver, dirty_idx, dirty_rows = view.drain_dirty()
            st = self._state
            if (st is None or ver != st["version"]
                    or view.num_nodes() > st["n_pad"]
                    or view.num_columns() > st["r_pad"]):
                self._full_sync(view)
                st = self._state
            elif dirty_idx:
                self._apply_deltas(dirty_idx, dirty_rows)
        if st is None or not st["node_ids"]:
            return False
        with tracing.span("scheduler.solve.classes", category="sched"):
            # Register any new scheduling classes (rare: classes are
            # interned resource shapes).  A class demanding an unknown
            # resource column forces the column into the view (version
            # bump -> full resync).
            tick = self.stats["ticks"]
            for cls in groups:
                self._class_last_used[cls] = tick
            new_classes = [c for c in groups if c not in self._class_rows]
            if new_classes and (len(self._class_reqs) + len(new_classes)
                                > self._demand_host.shape[0]):
                # Growth would widen c_cap (a recompile): first try to
                # reclaim rows from classes that have gone idle.
                self._evict_stale_classes(set(groups), st)
                if (len(self._class_reqs) + len(new_classes)
                        > self._MAX_CLASS_ROWS):
                    # Over the hard cap even after stale eviction: churn
                    # interned >4096 classes inside the idle window.
                    # Evict LRU rows regardless of idleness — only the
                    # classes live THIS tick are protected — before
                    # giving up.
                    self._evict_stale_classes(set(groups), st,
                                              force_lru=True)
                if (len(self._class_reqs) + len(new_classes)
                        > self._MAX_CLASS_ROWS):
                    return False
            for cls, members in groups.items():
                if cls not in self._class_rows:
                    req = specs[members[0]].resources
                    if any(name not in st["columns"]
                           for name in req.names()):
                        view.demand_matrix([req])   # creates columns
                        self._full_sync(view)
                        st = self._state
                    self._register_class(cls, req, st)
            c_cap = self._demand_host.shape[0]
            counts = np.zeros(c_cap, dtype=np.float32)
            for cls, members in groups.items():
                counts[self._class_rows[cls]] = len(members)
            total_q = int(counts.sum())
            nnz_bound = min(total_q, len(groups) * len(st["node_ids"]))
            nnz_max = next(
                (b for b in self._NNZ_BUCKETS if b >= nnz_bound), None)
            if nnz_max is None:
                return False
            cfg = get_config()
            cost = self._build_cost(specs, groups, st, c_cap, cfg)
        n_pad = st["n_pad"]
        if st.get("n_shards", 1) > 1:
            # Pod-sharded tick: every shard solves its node block
            # against the resident sharded world state.
            from ray_tpu.scheduler import sharded_solve
            self.last_path = f"sharded[{st['n_shards']}]/jnp"
            self._note_program(c_cap, n_pad, st["r_pad"], nnz_max,
                               self.last_path)
            with tracing.span("scheduler.solve.dispatch", category="sched"):
                merged = sharded_solve.solve_tick_sharded(
                    st["avail_t"], st["total_t"], self._demand_dev,
                    counts, st["accel_node"], self._accel_dev,
                    cfg.scheduler_spread_threshold, cost, c_cap, n_pad,
                    st["r_pad"], nnz_max, st["n_shards"])
            self.stats["sharded_ticks"] += 1
            if not merged["ok"]:
                return False
            idx, vals = merged["idx"], merged["vals"]
        else:
            use_pallas = _pallas_enabled()
            self.last_path = f"single/{_fill_name(use_pallas)}"
            self._note_program(c_cap, n_pad, st["r_pad"], nnz_max,
                               self.last_path)
            with tracing.span("scheduler.solve.dispatch", category="sched"):
                # Upload of counts/cost, lookup or compile of the
                # program, launch: returns before the device finishes.
                packed = _jit_solve_tick(
                    c_cap, n_pad, st["r_pad"], nnz_max, use_pallas)(
                        st["avail_t"], st["total_t"], self._demand_dev,
                        counts, st["accel_node"], self._accel_dev,
                        np.float32(cfg.scheduler_spread_threshold), cost)
            with tracing.span("scheduler.solve.fetch", category="sched"):
                packed = np.asarray(packed)
            idx, vals, _, ok, _ = _unpack_tick(packed, nnz_max)
            if not ok:
                return False
        with tracing.span("scheduler.solve.expand", category="sched"):
            # Decode the sparse assignment and expand per-spec targets.
            alloc = _dense_alloc(idx, vals, c_cap, n_pad)
            node_ids = st["node_ids"]
            n_real = len(node_ids)
            for cls, members in groups.items():
                row = alloc[self._class_rows[cls]]
                k = 0
                for n in range(n_real):
                    for _ in range(int(row[n])):
                        if k < len(members):
                            targets[members[k]] = node_ids[n]
                            k += 1
        return True

    def _note_program(self, *key) -> None:
        """Count the distinct solve programs this session has asked for
        (``stats["solve_programs"]``): a step in it inside a measured
        window is a compile, or a cache load, on the raylet's loop."""
        self._programs.add(key)
        self.stats["solve_programs"] = len(self._programs)

    def _build_cost(self, specs, groups, st, c_cap: int, cfg):
        """Per-(class, node) cost matrix for this tick, or the cached
        device-resident zeros when no cost term is live (the common
        case — nothing extra crosses host->device then).

        Two terms, both in utilization units (1/16 = one fill bucket):
          * heterogeneity (Gavel): ``w_het * (1 - rate/max_rate)`` from
            the node throughput labels, picked per class (accelerator
            classes read the accel rate) — slower nodes fill later;
          * arg-locality (Tesserae placement quality): ``-w_loc *
            bytes_on_node / max_bytes`` aggregated over the class's
            queued specs from the object directory's size hints —
            nodes already holding the class's argument bytes fill
            first, shrinking cross-node fetches.
        """
        w_het = cfg.scheduler_het_weight
        w_loc = cfg.scheduler_locality_weight
        het = st["het_active"] and w_het > 0.0
        loc_rows: Dict[int, Dict] = {}
        if w_loc > 0.0 and self._locality_provider is not None:
            for cls, members in groups.items():
                with_args = [specs[i] for i in members
                             if getattr(specs[i], "args", None)]
                if not with_args:
                    continue
                try:
                    by_node = self._locality_provider(with_args)
                except Exception:
                    by_node = None
                if by_node:
                    loc_rows[cls] = by_node
        if not het and not loc_rows:
            self.last_cost_active = False
            return self._zero_cost_dev
        self.last_cost_active = True
        self.stats["cost_ticks"] += 1
        n_pad = st["n_pad"]
        cost = np.zeros((c_cap, n_pad), dtype=np.float32)
        if het:
            accel = self._accel_host
            cost[:] = np.where(accel[:, None], st["het_accel"][None, :],
                               st["het_cpu"][None, :]) * np.float32(w_het)
        node_index = st["node_index"]
        for cls, by_node in loc_rows.items():
            row = self._class_rows.get(cls)
            if row is None:
                continue
            top = max(by_node.values())
            if top <= 0:
                continue
            for nid, nbytes in by_node.items():
                idx = node_index.get(nid)
                if idx is not None:
                    cost[row, idx] -= np.float32(w_loc) * \
                        np.float32(nbytes / top)
        return cost

    def _full_sync(self, view):
        import jax
        self.stats["full_syncs"] += 1
        ver, node_ids, total, avail, columns = view.snapshot_versioned()
        N, R = total.shape
        prev = self._state
        # Pod-sharded residency: above the gate the world state shards
        # along the node axis across the local mesh; every shard stays
        # device-resident between ticks exactly like the single-chip
        # path (deltas scatter into the sharded array, see
        # _apply_deltas).
        from ray_tpu.scheduler import sharded_solve
        n_shards = sharded_solve.plan_shards(N)
        # Keep padded dims monotone to avoid recompiles on node churn;
        # the sharded ring additionally pads to whole groups per shard.
        n_pad = _round_up(max(N, 8), _GROUP * n_shards)
        r_pad = _round_up(max(R, 1), 8)
        if prev is not None:
            n_pad = _round_up(max(n_pad, prev["n_pad"]),
                              _GROUP * n_shards)
            r_pad = max(r_pad, prev["r_pad"])
        accel_node = accelerator_node_mask(total)
        # Per-node throughput rates (heterogeneity cost term): read once
        # per structural change from node labels.  Normalized to the
        # fleet max so homogeneous fleets cost uniformly zero; padded
        # nodes carry the max rate (zero cost — they are masked out by
        # the empty bucket anyway).
        rates_cpu = np.ones(n_pad, dtype=np.float32)
        rates_accel = np.ones(n_pad, dtype=np.float32)
        for i, nid in enumerate(node_ids):
            res = view.node_resources(nid)
            labels = getattr(res, "labels", None) or {}
            r = _label_rate(labels, NODE_THROUGHPUT_LABEL)
            rates_cpu[i] = r
            rates_accel[i] = _label_rate(
                labels, NODE_ACCEL_THROUGHPUT_LABEL, default=r)
        rates_cpu[N:] = rates_cpu[:max(N, 1)].max()
        rates_accel[N:] = rates_accel[:max(N, 1)].max()
        het_cpu = 1.0 - rates_cpu / rates_cpu.max()
        het_accel = 1.0 - rates_accel / rates_accel.max()
        if n_shards > 1:
            sh_rn = sharded_solve.node_sharding(n_shards)
            sh_n = sharded_solve.node_sharding(n_shards, ("nodes",))
        else:
            sh_rn = sh_n = None
        self._state = {
            "version": ver, "node_ids": node_ids, "columns": columns,
            "node_index": {nid: i for i, nid in enumerate(node_ids)},
            "n_pad": n_pad, "r_pad": r_pad, "n_shards": n_shards,
            "het_cpu": het_cpu.astype(np.float32),
            "het_accel": het_accel.astype(np.float32),
            "het_active": bool(het_cpu.any() or het_accel.any()),
            "avail_t": jax.device_put(
                _pad_to(avail.astype(np.float32), (n_pad, r_pad)).T.copy(),
                sh_rn),
            "total_t": jax.device_put(
                _pad_to(total.astype(np.float32), (n_pad, r_pad)).T.copy(),
                sh_rn),
            "accel_node": jax.device_put(_pad_to(accel_node, (n_pad,)),
                                         sh_n),
        }
        # Rebuild the demand matrix against the (possibly wider) column
        # mapping.
        self._rebuild_demand(columns, r_pad)

    def _rebuild_demand(self, columns: Dict[str, int], r_pad: int):
        import jax
        c_cap = max(8, _round_up(max(len(self._class_reqs), 1), 8))
        demand = np.zeros((c_cap, r_pad), dtype=np.float32)
        accel = np.zeros(c_cap, dtype=bool)
        for row, req in enumerate(self._class_reqs):
            for name, v in req.to_dict().items():
                col = columns.get(name)
                if col is not None:
                    demand[row, col] = v
            accel[row] = req.uses_accelerator()
        self._demand_host, self._accel_host = demand, accel
        n_shards = self._state["n_shards"] if self._state else 1
        if n_shards > 1:
            from ray_tpu.scheduler import sharded_solve
            rep = sharded_solve.replicated_sharding(n_shards)
            cost_sh = sharded_solve.node_sharding(n_shards)
        else:
            rep = cost_sh = None
        self._demand_dev = jax.device_put(demand, rep)
        self._accel_dev = jax.device_put(accel, rep)
        # Device-resident zero cost matrix: the common no-cost tick
        # passes this cached handle, so nothing extra crosses
        # host->device unless a locality/heterogeneity term is live.
        n_pad = self._state["n_pad"] if self._state else _GROUP
        self._zero_cost_dev = jax.device_put(
            np.zeros((c_cap, n_pad), dtype=np.float32), cost_sh)

    def _evict_stale_classes(self, keep: set, st: dict,
                             force_lru: bool = False) -> bool:
        """Compact the demand matrix by dropping rows for classes unused
        for _CLASS_IDLE_TICKS ticks (never ones in ``keep`` — the
        classes scheduling right now).  With ``force_lru`` the idle
        threshold is ignored and everything outside ``keep`` goes (the
        over-hard-cap path).  Returns True if anything moved.  Eviction
        only costs a cheap re-registration if the class ever reappears;
        it never affects correctness."""
        tick = self.stats["ticks"]
        row_to_cls = {row: c for c, row in self._class_rows.items()}
        survivors = []
        for row in range(len(self._class_reqs)):
            cls = row_to_cls[row]
            idle = tick - self._class_last_used.get(cls, tick)
            if cls in keep or (not force_lru
                               and idle < self._CLASS_IDLE_TICKS):
                survivors.append((cls, self._class_reqs[row]))
        if len(survivors) == len(self._class_reqs):
            return False
        self.stats["class_evictions"] += \
            len(self._class_reqs) - len(survivors)
        self._class_rows = {c: i for i, (c, _) in enumerate(survivors)}
        self._class_reqs = [req for _, req in survivors]
        self._class_last_used = {
            c: self._class_last_used.get(c, tick) for c, _ in survivors}
        self._rebuild_demand(st["columns"], st["r_pad"])
        return True

    def _register_class(self, cls: int, req, st: dict):
        import jax
        row = len(self._class_reqs)
        self._class_rows[cls] = row
        self._class_reqs.append(req)
        if row >= self._demand_host.shape[0]:
            self._rebuild_demand(st["columns"], st["r_pad"])
            return
        for name, v in req.to_dict().items():
            col = st["columns"].get(name)
            if col is not None:
                self._demand_host[row, col] = v
        self._accel_host[row] = req.uses_accelerator()
        # Class registration is rare; re-uploading the (small) demand
        # matrix wholesale is simpler than a device scatter.
        rep = None
        if st.get("n_shards", 1) > 1:
            from ray_tpu.scheduler import sharded_solve
            rep = sharded_solve.replicated_sharding(st["n_shards"])
        self._demand_dev = jax.device_put(self._demand_host, rep)
        self._accel_dev = jax.device_put(self._accel_host, rep)

    def _apply_deltas(self, dirty_idx: List[int], dirty_rows: np.ndarray):
        import jax
        st = self._state
        self.stats["row_deltas"] += len(dirty_idx)
        n_pad, r_pad = st["n_pad"], st["r_pad"]
        n_shards = st.get("n_shards", 1)
        if len(dirty_idx) > n_pad // 2:
            # Cheaper to re-upload than to scatter half the matrix.
            sh = None
            if n_shards > 1:
                from ray_tpu.scheduler import sharded_solve
                sh = sharded_solve.node_sharding(n_shards)
            avail = np.asarray(st["avail_t"]).T.copy()
            avail[dirty_idx, :dirty_rows.shape[1]] = dirty_rows
            st["avail_t"] = jax.device_put(avail.T.copy(), sh)
            return
        k_pad = 1
        while k_pad < len(dirty_idx):
            k_pad *= 2
        idx = np.full(k_pad, dirty_idx[-1], dtype=np.int32)
        idx[:len(dirty_idx)] = dirty_idx
        rows = np.zeros((k_pad, r_pad), dtype=np.float32)
        rows[:, :dirty_rows.shape[1]] = dirty_rows[-1]
        rows[:len(dirty_idx), :dirty_rows.shape[1]] = dirty_rows
        if n_shards > 1:
            from ray_tpu.scheduler import sharded_solve
            fn = sharded_solve._jit_sharded_apply_rows(
                n_pad, r_pad, k_pad, n_shards)
        else:
            fn = _jit_apply_rows(n_pad, r_pad, k_pad)
        st["avail_t"] = fn(st["avail_t"], idx, rows)
