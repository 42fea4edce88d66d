"""What a rematerialised layer keeps: the plan behind
``models.transformer.remat_layer``.

A layer scanned under ``jax.checkpoint`` keeps its input and whatever
its policy names; the backward pass computes the rest a second time.
What the kernels name of their own outputs (``BASE_NAMES``: the flash
kernel's ``out`` and ``lse``, the delta rule's ``o`` and the state
entering each of its grid steps, the state-space rule's ``y`` and its
step states, KDA's ``o`` and its step states) is kept always: with them
a layer's backward pass does not run the forward kernel again, for
about the bytes of the layer's input a kernel.  Beyond them the layers name their
cut points (``checkpoint_name``: the middle residual, q, k and v as they
enter the kernel, the FFN's products, the latents, the delta layer's
projections and convolution, the state-space layer's projections,
convolution and scan output, the Mamba-2 mixer's three projections
and convolution, the experts' latent, the memory unit's gate, differential
attention's q and keys and values, the router's scores ...), and this
module decides, where the step is traced, which of those names the
device has room for.

**What is traced when** (``value_and_grad``, which ``make_train_step``
calls in the place of ``jax.value_and_grad``):

* A device that reports no memory limit (the CPU, the interpreter): the
  objective is differentiated as it always was, every ``remat_layer``
  has the policy ``save_only_these_names(*BASE_NAMES)``, and nothing
  here runs.
* Otherwise the objective is traced **once**, forward only
  (``jax.make_jaxpr``), with every ``remat_layer`` under a policy that
  is bound late (``Keeps``).  A forward trace consults no policy, so the
  jaxpr is the step's own whatever the plan will be.  The plan is read
  from that jaxpr -- no layer's Python and no kernel body is traced for
  it -- and given to the policies; then the jaxpr, not the Python, is
  differentiated (``jaxpr_as_fun`` under ``jax.value_and_grad``: the
  scans' bodies go through the same rules as in a direct trace, and the
  few equations outside them are bound a second time).  So a planned
  step calls each layer function, and traces each kernel's forward
  body, as often as an unplanned one.
* A second trace of the same step (same shapes) takes its plan from the
  first (``plans``): the program does not depend on what the device
  held at the later moment.

**The plan.**

* ``_find_runs`` walks the objective's jaxpr for the ``checkpoint``
  equations that carry a ``Keeps``: each is a run of layers, as many as
  the scans around it are long (a period's repeats times a run's
  length), its carry what the scan stacks a layer.
* ``survey`` reads one such layer's forward jaxpr: what each named value
  weighs on this device, and which equations the backward's operands
  hang on without it.  The backward is not traced; what it reads is
  taken from the forward's equations by rule (``_read_by_the_backward``:
  a product's transpose reads its operands, a kernel's backward its
  inputs, a norm's its input; what is linear reads nothing), and
  everything light fuses and is made again in place.
  ``recompute_work`` prices the equations: a product's FLOPs as the
  bytes moved in their time, and the bytes of the arrays that reach
  memory between the cut before a name and the name.
* ``worth_order`` puts the candidates of all the step's runs in the
  order of work avoided per byte kept, each counted given what is ahead
  of it in the order (a value upstream of a kept one avoids less), and
  leaves out what avoids no more than keeping it moves.
* ``make_plan`` keeps the longest prefix of that order that fits a
  budget in bytes: ``bytes_limit`` less what is resident when the step
  is traced (``bytes_in_use``: the state and whatever else the caller
  holds) less what the step itself holds whatever is kept
  (``step_bytes``: today's stacks, and the larger of the head's working
  set and of the gradients beside one layer's backward), times
  ``SAFETY``.

The plan is per run (its kind of layer, its shapes, its length); it is
an observation of shapes and of the device, not a setting: there is
nothing to configure.  What it cannot see: under a mesh the working
sets are counted whole, not a device's share (less is kept than would
fit); the selective scan's entering states, which carry no name
(``ops/selective_scan.py``: a layer's backward runs the forward kernel
again).  What one run hands to later runs (the shared slot of
``models.transformer.run_stacks``) is no candidate: it is an output of
its scan and a constant of its readers', kept once whatever the plan.
"""

from __future__ import annotations

import contextvars
import dataclasses
import math
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.extend import core as jex_core

from ray_tpu.ops.flash_attention import RESIDUAL_NAMES as _FLASH_NAMES
from ray_tpu.ops.gated_delta import RESIDUAL_NAMES as _DELTA_NAMES
from ray_tpu.ops.kda import RESIDUAL_NAMES as _KDA_NAMES
from ray_tpu.ops.ssd import RESIDUAL_NAMES as _SSD_NAMES

#: The kernels' own names: kept by every ``remat_layer`` whatever the
#: plan, counted in a run's stacks, no candidate.  A name occurs only in
#: the layers that run its kernel.
BASE_NAMES = _FLASH_NAMES + _DELTA_NAMES + _SSD_NAMES + _KDA_NAMES

#: Of the room the count leaves, the share the plan may fill.  A byte
#: kept costs XLA's heap about 1.11 (2.92 GB of stacks raised the
#: block-diffusion step's ``peak_memory_in_bytes`` by 3.24 GB: padding
#: and fragmentation), the runtime keeps 258 MiB of the limit to itself,
#: and the count of what the step holds anyway (``step_bytes``) is of
#: arrays as the jaxpr has them, not as XLA lays them out: a step that
#: came out over its count has to fit too (PERF.md section 6, PR 38 and
#: PR 39).
SAFETY = 0.8
#: Bytes moved for a byte kept: the array is copied into its stack in
#: the forward scan (read, written) and out of it through a slice in the
#: backward one (read, written).  A candidate has to avoid more than
#: that: the dense step's four kept arrays a layer cost 5 bytes moved a
#: byte kept where a transpose rode along (PERF.md section 6, PR 38).
_KEPT_BYTE_MOVES = 4.0
#: FLOPs the chip does in the time it moves a byte to or from HBM (the
#: v5e's 197 TFLOP/s over 819 GB/s): one unit for a product's work and
#: an elementwise pass's.  Only the order of the candidates depends on
#: it.
_FLOPS_A_BYTE = 240.0

# Equations that carry a jaxpr to be read in their place.
_INLINED = {"jit": "jaxpr", "closed_call": "call_jaxpr",
            "custom_jvp_call": "call_jaxpr", "custom_vjp_call": "call_jaxpr",
            "remat2": "jaxpr"}
# Primitives whose operands and results reach memory whatever surrounds
# them; everything else is taken to fuse with its consumers.
_HEAVY = frozenset({
    "dot_general", "ragged_dot", "ragged_dot_general",
    "conv_general_dilated", "gather", "scatter", "scatter-add", "sort",
    "top_k", "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
    "dynamic_update_slice", "psum", "all_gather", "ppermute", "all_to_all",
    "psum_scatter", "pallas_call", "scan", "while", "cond", "shard_map"})
_BODIES = frozenset({"scan", "while", "cond", "shard_map"})
# Light primitives whose transpose reads none of their operands: the
# backward hangs on the operands of every other one.
_LINEAR = frozenset({
    "add", "sub", "neg", "add_any", "convert_element_type", "reshape",
    "transpose", "broadcast_in_dim", "slice", "dynamic_slice", "concatenate",
    "squeeze", "expand_dims", "reduce_sum", "copy", "copy_p", "pad", "rev",
    "split", "stop_gradient", "iota", "eq", "ne", "lt", "le", "gt", "ge",
    "and", "or", "not", "sign", "floor", "ceil", "round", "is_finite",
    "argmax", "argmin", "reduce_and", "reduce_or", "sharding_constraint",
    "mesh_cast", "pvary", "axis_index"})


class _Val:
    """One array of a flattened jaxpr."""
    __slots__ = ("bytes", "floating", "name", "made_by")

    def __init__(self, aval):
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        self.bytes, self.floating = 0, False
        if shape is not None and dtype is not None:
            self.bytes = math.prod(shape) * getattr(dtype, "itemsize", 0)
            self.floating = jax.dtypes.issubdtype(dtype, np.inexact)
        self.name: Optional[str] = None
        self.made_by: Optional["_Eqn"] = None


@dataclasses.dataclass(eq=False)
class _Eqn:
    prim: str
    ins: List[_Val]
    outs: List[_Val]
    work: float          # bytes moved + FLOPs / _FLOPS_A_BYTE
    inner_bytes: int = 0  # what a loop's body holds (``_held``)


def _dot_flops(eqn) -> float:
    """The multiply-adds of a product, twice."""
    prim = eqn.primitive.name
    if prim not in ("dot_general", "ragged_dot", "ragged_dot_general"):
        return 0.0
    out = math.prod(eqn.outvars[0].aval.shape)
    lhs = eqn.invars[0].aval.shape
    # (float32 operands in earnest are six passes of the MXU)
    passes = 6.0 if (eqn.invars[0].aval.dtype.itemsize >= 4 and "HIGHEST"
                     in str(eqn.params.get("precision"))) else 1.0
    if prim == "dot_general":
        (contract, _), _ = eqn.params["dimension_numbers"]
        return passes * 2.0 * out * math.prod(lhs[d] for d in contract)
    return passes * 2.0 * out * lhs[-1]


def _sub_jaxprs(eqn):
    """(jaxpr, times it runs) of every jaxpr an equation carries."""
    times = 1
    if eqn.primitive.name == "scan":
        times = eqn.params["length"]
    elif eqn.primitive.name == "pallas_call":
        grid = getattr(eqn.params.get("grid_mapping"), "grid", ())
        times = math.prod(g for g in grid if isinstance(g, int))
    for value in eqn.params.values():
        for item in (value if isinstance(value, (tuple, list)) else (value,)):
            inner = getattr(item, "jaxpr", item)
            if isinstance(inner, jex_core.Jaxpr):
                yield inner, times


def _flops(jaxpr) -> float:
    """The products of a jaxpr and of what it calls."""
    return sum(map(_eqn_flops, jaxpr.eqns))


def _eqn_flops(eqn) -> float:
    """An equation's own product and those of the jaxprs it carries (of
    a ``cond``: its dearest branch)."""
    inner = [times * _flops(j) for j, times in _sub_jaxprs(eqn)]
    return _dot_flops(eqn) + (max(inner, default=0.0)
                              if eqn.primitive.name == "cond" else sum(inner))


_LITERAL = _Val(None)        # a constant in an equation: nobody's bytes


def _flatten(jaxpr, env: Dict[Any, _Val], out: List[_Eqn]) -> None:
    """``jaxpr``'s equations onto ``out`` with calls read in place and
    ``name`` equations as tags on their values; ``env``: the jaxpr's
    variables -> values (its inputs are in it already)."""
    def read(atom):
        if isinstance(atom, jex_core.Literal):
            return _LITERAL
        if atom not in env:                  # a constant of the jaxpr
            env[atom] = _Val(atom.aval)
        return env[atom]

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [read(a) for a in eqn.invars]
        if prim == "name":
            if ins[0] is not _LITERAL and ins[0].name is None:
                ins[0].name = eqn.params["name"]
            env[eqn.outvars[0]] = ins[0]
            continue
        if prim in _INLINED:
            inner = eqn.params[_INLINED[prim]]
            inner = getattr(inner, "jaxpr", inner)
            sub = dict(zip(inner.invars, ins))
            _flatten(inner, sub, out)
            for outer, result in zip(eqn.outvars, inner.outvars):
                env[outer] = _LITERAL if isinstance(
                    result, jex_core.Literal) else sub[result]
            continue
        outs = [_Val(v.aval) for v in eqn.outvars]
        env.update(zip(eqn.outvars, outs))
        work, inner_bytes = 0.0, 0
        if prim in _HEAVY:
            work = (sum(v.bytes for v in {id(v): v for v in (*ins, *outs)}
                        .values()) + _eqn_flops(eqn) / _FLOPS_A_BYTE)
            if prim in _BODIES:
                inner_bytes = max((_held(*_flat_of(j))
                                   for j, _ in _sub_jaxprs(eqn)), default=0)
        made = _Eqn(prim, ins, outs, work, inner_bytes)
        for value in outs:
            value.made_by = made
        out.append(made)


def _flat_of(jaxpr) -> Tuple[List[_Eqn], List[_Val], List[_Val]]:
    """(equations, inputs, outputs) of ``jaxpr`` flattened."""
    env = {v: _Val(v.aval) for v in (*jaxpr.constvars, *jaxpr.invars)}
    inputs = list(env.values())
    eqns: List[_Eqn] = []
    _flatten(jaxpr, env, eqns)
    outputs = [env[v] for v in jaxpr.outvars
               if not isinstance(v, jex_core.Literal)]
    return eqns, inputs, outputs


def _reaches_memory(eqns: Sequence[_Eqn], outputs: Sequence[_Val]) -> set:
    """The values of ``eqns`` that are arrays in memory: made or read by
    a heavy equation, named, or results; the others fuse away."""
    there = {id(v) for v in outputs}
    for eqn in eqns:
        heavy = eqn.prim in _HEAVY
        for value in eqn.outs:
            if heavy or value.name is not None:
                there.add(id(value))
        if heavy:
            there.update(id(v) for v in eqn.ins)
    return there


def _held(eqns: Sequence[_Eqn], inputs: Sequence[_Val],
          outputs: Sequence[_Val]) -> int:
    """What a run of equations may hold at once beside its operands:
    every array of it that reaches memory, none taken to be freed before
    the end (XLA's heap shares less inside a loop than the order of the
    equations would let it: PERF.md section 6, PR 38)."""
    there = _reaches_memory(eqns, outputs)
    mine = {id(v) for v in inputs}
    return sum(e.inner_bytes + sum(v.bytes for v in e.outs if id(v) in there
                                   and id(v) not in mine) for e in eqns)


def _peak(eqns: Sequence[_Eqn], inputs: Sequence[_Val],
          outputs: Sequence[_Val]) -> int:
    """The most bytes live at once over ``eqns`` in their order: arrays
    that reach memory from where they are made to their last reader (a
    fused value's operands live as long as it does), ``outputs`` to the
    end, what a loop's body holds while it runs.  ``inputs`` are
    somebody else's bytes."""
    there = _reaches_memory(eqns, outputs)
    there -= {id(v) for v in inputs}
    last: Dict[int, int] = {id(v): len(eqns) for v in outputs}
    for i in range(len(eqns) - 1, -1, -1):
        until = i
        for value in eqns[i].outs:
            if id(value) not in there:       # fused: lives in its readers
                until = max(until, last.get(id(value), i))
        for value in eqns[i].ins:
            last[id(value)] = max(last.get(id(value), -1), until)
    dies: Dict[int, int] = {}               # equation -> bytes it frees
    live = peak = 0
    for i, eqn in enumerate(eqns):
        for value in eqn.outs:
            if id(value) in there:
                live += value.bytes
                at = last.get(id(value), i)     # made and never read: here
                dies[at] = dies.get(at, 0) + value.bytes
        peak = max(peak, live + eqn.inner_bytes)
        live -= dies.pop(i, 0)
    return peak


def _read_by_the_backward(eqns: Sequence[_Eqn], inputs: Sequence[_Val]
                          ) -> Dict[int, _Val]:
    """The forward values a backward pass would read, by the rules of
    the equations alone (the backward is not traced): the operands of a
    heavy equation (a product's transpose reads them, a kernel's
    backward its inputs); of a product or quotient the operand beside
    one that a gradient reaches; a ``select``'s predicate; the operand
    of any other light equation that is not linear.  A gradient reaches
    every floating input and whatever is made from one."""
    reached = {id(v) for v in inputs if v.floating}
    needed: Dict[int, _Val] = {}
    for eqn in eqns:
        ins = eqn.ins
        live = [id(v) in reached for v in ins]
        if any(live) and eqn.prim != "stop_gradient":
            reached.update(id(v) for v in eqn.outs if v.floating)
        if eqn.prim in _HEAVY:
            read = ins
        elif eqn.prim == "mul":
            read = [ins[1]] * live[0] + [ins[0]] * live[1]
        elif eqn.prim == "div":
            read = [ins[1]] * (live[0] or live[1]) + [ins[0]] * live[1]
        elif eqn.prim == "select_n":
            read = ins[:1] * any(live[1:])
        elif eqn.prim in _LINEAR:
            read = ()
        else:
            read = [v for v, on in zip(ins, live) if on]
        needed.update((id(v), v) for v in read if v is not _LITERAL)
    return needed


@dataclasses.dataclass(eq=False)
class LayerSurvey:
    """One run of scanned layers as the planner sees it."""
    kind: Tuple[str, ...]
    layers: int                      # layers this run keeps stacks for
    names: Dict[str, int]            # candidate -> bytes a layer, a device
    stack_bytes: int = 0             # a layer's input and BASE_NAMES
    working_bytes: int = 0           # a layer's forward again and backward
    _needed: List[_Val] = dataclasses.field(default_factory=list)
    _named: Dict[str, List[_Val]] = dataclasses.field(default_factory=dict)

    def recompute_work(self, kept: Sequence[str] = ()) -> float:
        """The forward work one layer's backward does a second time when
        the run keeps ``kept`` beside ``BASE_NAMES`` (bytes moved, a
        product's FLOPs as the bytes moved in their time)."""
        return sum(e.work for e in self.recomputed(kept))

    def recomputed(self, kept: Sequence[str] = ()) -> List[_Eqn]:
        """The forward's equations that the backward's operands hang on,
        back to the layer's arguments and to what is kept."""
        seen = {id(v) for n in (*BASE_NAMES, *kept)
                for v in self._named.get(n, ())}
        marked: Dict[int, _Eqn] = {}
        stack = list(self._needed)
        while stack:
            value = stack.pop()
            if id(value) in seen:
                continue
            seen.add(id(value))
            eqn = value.made_by
            if eqn is not None and id(eqn) not in marked:
                marked[id(eqn)] = eqn
                stack.extend(eqn.ins)
        return list(marked.values())


def survey(layer_jaxpr, layers: int = 1, kind: Tuple[str, ...] = (),
           carry_bytes: int = 0, shards: int = 1) -> LayerSurvey:
    """The forward jaxpr of one layer as ``jax.checkpoint`` holds it ->
    its survey.  ``layers``: how many of it keep stacks; ``carry_bytes``:
    what the scan stacks a layer whatever is kept; ``shards``: the
    devices the token axes are split over (a named value's bytes on one
    device are its bytes over them).  Nothing is traced."""
    eqns, inputs, outputs = _flat_of(layer_jaxpr)
    needed = _read_by_the_backward(eqns, inputs)
    named: Dict[str, List[_Val]] = {}    # (a name may be on several)
    for eqn in eqns:
        for value in eqn.outs:
            if value.name is not None:
                named.setdefault(value.name, []).append(value)
    # a light equation costs what reaches memory of it: written, read
    there = _reaches_memory(eqns, needed.values())
    for eqn in eqns:
        if eqn.prim not in _HEAVY:
            eqn.work = 2.0 * sum(v.bytes for v in eqn.outs
                                 if id(v) in there)

    def a_device(values):
        return -(-sum(v.bytes for v in values) // shards)

    out = LayerSurvey(
        kind=tuple(kind), layers=layers,
        names={n: a_device(values) for n, values in named.items()
               if n not in BASE_NAMES and a_device(values)},
        stack_bytes=-(-carry_bytes // shards) + a_device(
            [v for n in BASE_NAMES for v in named.get(n, ())]),
        _needed=list(needed.values()), _named=named)
    # what the backward scan's body holds: the forward it runs again,
    # every array of it alive until its cotangent is made, and as much
    # again of cotangents as the forward's arrays are at their most
    again = out.recomputed()
    out.working_bytes = (_held(again, inputs, outputs)
                         + _peak(eqns, inputs, outputs))
    return out


@dataclasses.dataclass(frozen=True)
class Candidate:
    run: int                 # index of its LayerSurvey
    name: str
    bytes: int               # over the run's layers, a device
    work: float              # avoided over the run's layers, given the
    #                          candidates ahead of it in the order


def worth_order(surveys: Sequence[LayerSurvey]) -> List[Candidate]:
    """Every run's candidates in the order of work avoided per byte
    kept, each given those ahead of it; a name that avoids no more than
    keeping it costs is left out."""
    kept: List[List[str]] = [[] for _ in surveys]
    now = [s.recompute_work() for s in surveys]
    left = [set(s.names) for s in surveys]

    def best_of(r):
        # (ties: the name, so that the order is one)
        s = surveys[r]
        return max(((now[r] - s.recompute_work((*kept[r], name)))
                    / s.names[name], name) for name in left[r])

    best = {r: best_of(r) for r in range(len(surveys)) if left[r]}
    order: List[Candidate] = []
    while best:
        # a run's ratios change only when that run keeps one more
        r = max(best, key=lambda r: (best[r][0], -r))
        ratio, name = best[r]
        if ratio <= _KEPT_BYTE_MOVES:
            break
        s = surveys[r]
        gain = ratio * s.names[name]
        left[r].discard(name)
        kept[r].append(name)
        now[r] -= gain
        order.append(Candidate(r, name, s.names[name] * s.layers,
                               gain * s.layers))
        if left[r]:
            best[r] = best_of(r)
        else:
            del best[r]
    return order


def make_plan(surveys: Sequence[LayerSurvey], budget_bytes: Optional[int]
              ) -> Tuple[List[Tuple[str, ...]], Dict[str, Any]]:
    """The longest prefix of ``worth_order`` whose bytes fit
    ``budget_bytes`` -> (the names each run keeps beside ``BASE_NAMES``,
    the plan as the registry publishes it).  None or nothing: no room is
    known, and every run keeps ``BASE_NAMES`` alone."""
    order = worth_order(surveys) if budget_bytes and budget_bytes > 0 else []
    kept: List[List[str]] = [[] for _ in surveys]
    total, cut = 0, 0
    for c in order:
        if total + c.bytes > budget_bytes:
            break
        total, cut = total + c.bytes, cut + 1
        kept[c.run].append(c.name)
    runs = [{"kind": "+".join(s.kind), "layers": s.layers,
             "names": list(kept[r]),
             "bytes_a_layer": sum(s.names[n] for n in kept[r]),
             "refused": [[c.name, c.bytes] for c in order[cut:]
                         if c.run == r]}
            for r, s in enumerate(surveys)]
    return [tuple(k) for k in kept], {
        "budget_bytes": budget_bytes, "kept_bytes": total, "runs": runs}


def no_plan() -> Dict[str, Any]:
    """What is published where nothing was planned."""
    return make_plan((), None)[1]


def device_memory(mesh=None) -> Optional[Tuple[int, int]]:
    """(``bytes_limit``, ``bytes_in_use``) of the device the step runs
    on (the first of the mesh's that this process holds), or None where
    it reports none (the CPU)."""
    devices = jax.local_devices() if mesh is None else [
        d for d in mesh.devices.flat
        if d.process_index == jax.process_index()]
    stats = (devices[0].memory_stats() if devices else None) or {}
    if not stats.get("bytes_limit"):
        return None
    return int(stats["bytes_limit"]), int(stats.get("bytes_in_use", 0))


class Keeps:
    """The policy of one ``remat_layer`` in a planned trace: it saves
    the names the plan gives it, and is asked only when the trace is
    differentiated, after the plan is made."""

    def __init__(self, kind: Tuple[str, ...], shards: int):
        self.kind, self.shards = tuple(kind), shards
        self.names: Tuple[str, ...] = BASE_NAMES
        self._saves = None

    def keep(self, names: Sequence[str]) -> None:
        assert self._saves is None, "asked before it was planned"
        self.names = BASE_NAMES + tuple(names)

    def __call__(self, *args, **params):
        if self._saves is None:
            self._saves = jax.checkpoint_policies.save_only_these_names(
                *self.names)
        return self._saves(*args, **params)

    def __repr__(self):
        return f"Keeps({'+'.join(self.kind)})"


_collecting: contextvars.ContextVar = contextvars.ContextVar(
    "remat_collecting", default=False)


def policy(kind: Tuple[str, ...] = (), mesh=None):
    """The ``jax.checkpoint`` policy of a ``remat_layer``: one the plan
    will fill where a step is being planned, else the one that saves
    ``BASE_NAMES``."""
    if not _collecting.get():
        return jax.checkpoint_policies.save_only_these_names(*BASE_NAMES)
    shards = 1 if mesh is None else (mesh.shape.get("dp", 1)
                                     * mesh.shape.get("sp", 1))
    return Keeps(kind, shards)


def _find_runs(jaxpr, times: int = 1, carried=frozenset(), found=None):
    """[(its ``Keeps``, the layer's jaxpr, layers, bytes of the carry)]
    of every planned ``checkpoint`` equation under ``jaxpr``, in the
    order they come; a scan's length multiplies what is inside it."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if isinstance(eqn.params.get("policy"), Keeps):
            found.append((eqn.params["policy"], eqn.params["jaxpr"], times,
                          sum(_Val(v.aval).bytes for v in eqn.invars
                              if v in carried)))
        elif eqn.primitive.name == "scan":
            body = eqn.params["jaxpr"].jaxpr
            first = eqn.params["num_consts"]
            _find_runs(body, times * eqn.params["length"], frozenset(
                body.invars[first:first + eqn.params["num_carry"]]), found)
        else:
            for inner, _ in _sub_jaxprs(eqn):
                _find_runs(inner, times, frozenset(), found)
    return found


def step_bytes(objective_jaxpr, grad_bytes: int,
               runs: Sequence[LayerSurvey]) -> int:
    """What a step holds on the device beside its arguments whatever its
    runs keep beyond ``BASE_NAMES``: the runs' stacks, and the larger of
    the head's working set (the peak of live bytes over the objective's
    forward outside its scans, and as much again for its backward) and
    one layer's forward and backward beside the gradients."""
    stacks = sum(s.layers * s.stack_bytes for s in runs)
    eqns, inputs, outputs = _flat_of(objective_jaxpr)
    for eqn in eqns:             # the scans' bodies are ``layer``'s count
        eqn.inner_bytes = 0
    head = 2 * _peak(eqns, inputs, outputs)
    layer = max((s.working_bytes for s in runs), default=0)
    return stacks + max(head, grad_bytes + layer)


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def plan_step(objective_jaxpr, found, grad_bytes: int,
              memory: Tuple[int, int]
              ) -> Tuple[List[Tuple[str, ...]], Dict[str, Any]]:
    """The traced objective and its runs as ``_find_runs`` found them ->
    (the names each run keeps, in that order, the plan as published)."""
    started = time.perf_counter()
    runs = [survey(layer, layers, keeps.kind, carry, keeps.shards)
            for keeps, layer, layers, carry in found]
    limit, in_use = memory
    needs = step_bytes(objective_jaxpr, grad_bytes, runs)
    names, report = make_plan(runs, int(SAFETY * (limit - in_use - needs)))
    report.update(bytes_limit=limit, bytes_in_use=in_use, step_bytes=needs,
                  plan_seconds=time.perf_counter() - started)
    return names, report


def value_and_grad(objective: Callable, params, mesh=None,
                   plans: Optional[Dict] = None):
    """``jax.value_and_grad(objective, has_aux=True)(params)`` with what
    the ``remat_layer`` scans of ``objective`` keep planned by the room
    the device has -> (((loss, aux), grads), the plan as published).
    ``plans``: the caller's memory of the plans it got, by the shapes of
    what was traced (module docstring: what is traced when)."""
    memory = device_memory(mesh)
    if memory is None:
        return (jax.value_and_grad(objective, has_aux=True)(params),
                no_plan())
    started = time.perf_counter()
    token = _collecting.set(True)
    try:
        closed, shape = jax.make_jaxpr(objective, return_shape=True)(params)
    finally:
        _collecting.reset(token)
    traced = time.perf_counter() - started
    found = _find_runs(closed.jaxpr)
    plans = {} if plans is None else plans
    shapes = (tuple(map(str, closed.in_avals)),
              tuple(str(getattr(c, "aval", None)) for c in closed.consts))
    if shapes not in plans:
        plans[shapes] = plan_step(closed.jaxpr, found, _tree_bytes(params),
                                  memory)
    names, report = plans[shapes]
    for (keeps, *_), kept in zip(found, names):
        keeps.keep(kept)
    replay = jex_core.jaxpr_as_fun(closed)

    def traced_objective(p):
        return jax.tree.unflatten(jax.tree.structure(shape),
                                  replay(*jax.tree.leaves(p)))

    return (jax.value_and_grad(traced_objective, has_aux=True)(params),
            dict(report, trace_seconds=traced))
