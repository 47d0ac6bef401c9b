"""PallasBackend — compiles KVI programs onto fused Pallas kernels.

The Klessydra insight, translated to TPU: vector operands live in the SPM
across a whole *sequence* of vector instructions. Maximal runs of
element-wise instructions — the :class:`~repro.kvi.passes.fusion.
FusedRegion` plan computed by the ``fuse_regions`` pass and attached to
the program's metadata — are compiled into a **single fused
``pl.pallas_call``** each (one VMEM-resident slot file, one HBM read per
input window, one write per output window); reductions go through the
Pallas kdotp/kvred kernels; ``kmemld``/``kmemstr``/``kvcp`` are data
movement handled on the register file. This backend no longer derives
the fusion segmentation itself: it executes the plan handed to it,
re-planning (through the same planner) only when the program carries no
plan (``passes=()``) or one planned under different slot-file bounds.

Workload batching: a homogeneous :class:`~repro.kvi.workload.KviWorkload`
(N data instances of one program structure) executes as one **batch** —
every fused segment is ONE ``pallas_call`` over ``(N, n)`` tiles and every
reduction is one batched kernel launch. The whole walk — regions,
reductions and register-file moves — is traced into one jitted device
program per (structure, N) (:class:`CompiledWalk`), so a batch costs one
dispatch and one device->host fetch, and the compile is paid once.
Heterogeneous workloads are grouped by program structure and each group is
batched the same way.

``fused_elementwise_call`` is the public compile-and-run primitive for an
element-wise slot program. It supersedes the untyped tuple protocol that
used to live in ``repro.kernels.kvi_vops`` (kept there as a deprecation
shim).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_mode, pick_block
from repro.kernels.kdotp import reduce_rows
from repro.kvi.backend import (BackendBase, BackendResult, register_backend)
from repro.kvi.ir import (ELEMWISE_OPS, KviInstr, KviOp, KviProgram,
                          ScalarBlock, np_dtype)
from repro.kvi.obs import host
from repro.kvi.obs.host import host_span
from repro.kvi.passes.fusion import (MAX_FUSED_INPUTS, MAX_FUSED_OPS,
                                     META_KEY, FusedRegion, FusionPlan,
                                     plan_fusion_regions)
from repro.kvi.workload import (KviWorkload, WorkloadResult,
                                structural_signature)

# one fused element-wise slot instruction: (op, dst, src1, src2|None, imm)
SlotOp = Tuple[str, int, int, Optional[int], int]

_UNSIGNED = {jnp.int8.dtype: jnp.uint8, jnp.int16.dtype: jnp.uint16,
             jnp.int32.dtype: jnp.uint32}


@dataclass
class KernelCache:
    """Compiled-call cache: slot-program structure -> a ``jax.jit``-wrapped
    callable closing over its ``pl.pallas_call`` (or batched reduction
    kernel), and program structure -> its batch's :class:`CompiledWalk`,
    which calls those kernels. Keys carry everything baked into the
    trace — the op/slot program, batch shape, block split and dtype — so
    a hit is exactly a traced function's or compiled executable's reuse.

    Scoped to a :class:`PallasBackend` instance by default, so repeated
    ``run_workload`` calls — the serving engine's steady-state traffic,
    the DSE's warm-up iterations — pay zero recompiles; pass one cache
    to several backends to share it wider.

    ``misses`` counts builds, ``hits`` reuses: a warm batch is one hit.
    """

    hits: int = 0
    misses: int = 0
    _fns: Dict[tuple, Callable] = field(default_factory=dict)

    def get(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        fn = self._fns.get(key)
        if fn is None:
            self.misses += 1
            fn = self._fns[key] = build()
        else:
            self.hits += 1
        return fn

    def clear(self) -> None:
        """Drop every compiled entry and reset the counters."""
        self._fns.clear()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._fns)

    @property
    def stats(self) -> Dict[str, int]:
        return {"hits": self.hits, "misses": self.misses,
                "entries": len(self._fns)}

    def items(self):
        """The cached ``(key, jitted callable)`` pairs."""
        return self._fns.items()


def apply_vop(op: str, a, b, imm: int, bits: Optional[int] = None):
    """Element-wise KVI semantics shared by the fused kernel body and the
    jnp oracle (wrap-around integer arithmetic like the Klessydra MFU).

    ``bits`` is the element width when narrow values are carried
    sign-extended in a wider register (the fused kernel computes sub-word
    programs in int32 and truncates on store, which keeps the MFU's
    two's-complement wrap-around); it defaults to the dtype's width."""
    if op == "kaddv":
        return a + b
    if op == "ksubv":
        return a - b
    if op == "kvmul":
        return a * b
    if op == "ksvaddsc":
        return a + jnp.asarray(imm, a.dtype)
    if op == "ksvmulsc":
        return a * jnp.asarray(imm, a.dtype)
    if op == "ksrlv":
        width = 8 * jnp.dtype(a.dtype).itemsize
        bits = bits or width
        if not 0 <= imm < bits:
            return jnp.zeros_like(a)
        if bits == width:
            u = _UNSIGNED[jnp.dtype(a.dtype)]
            return (a.astype(u) >> jnp.asarray(imm, u)).astype(a.dtype)
        # zero-extend from the narrow width before the logical shift
        a = a & jnp.asarray((1 << bits) - 1, a.dtype)
        return jax.lax.shift_right_logical(a, jnp.asarray(imm, a.dtype))
    if op == "ksrav":
        # a shift past the width fills with the sign, as a shift by
        # width - 1 does
        width = 8 * jnp.dtype(a.dtype).itemsize
        return a >> jnp.asarray(imm if 0 <= imm < width else width - 1,
                                a.dtype)
    if op == "krelu":
        return jnp.maximum(a, jnp.asarray(0, a.dtype))
    if op == "kvslt":
        return (a < b).astype(a.dtype)
    if op == "ksvslt":
        return (a < jnp.asarray(imm, a.dtype)).astype(a.dtype)
    if op == "kvcp":
        return a
    raise ValueError(op)


def _fused_kernel(*refs, program: Tuple[SlotOp, ...], in_slots, out_slots,
                  n_slots: int, bits: int, wide):
    in_refs = refs[:len(in_slots)]
    out_refs = refs[len(in_slots):]
    # sub-word values ride sign-extended in int32; every result wraps back
    # to the element width (shift up, arithmetic shift down), so ops that
    # are not modular (krelu, compares, shifts) see what the MFU sees
    rewrap = 32 - bits if wide == jnp.int32 and bits < 32 else 0
    slots: List = [None] * n_slots
    for r, s in zip(in_refs, in_slots):
        slots[s] = r[...].astype(wide)
    for op, dst, s1, s2, imm in program:
        a = slots[s1]
        b = slots[s2] if s2 is not None else None
        v = apply_vop(op, a, b, imm, bits=bits)
        slots[dst] = (v << rewrap) >> rewrap if rewrap else v
    for r, s in zip(out_refs, out_slots):
        r[...] = slots[s].astype(r.dtype)


def _make_fused_caller(program: Tuple[SlotOp, ...], in_slots: tuple,
                       out_slots: tuple, n_slots: int, N: int, n: int,
                       bl: int, dt) -> Callable:
    """A callable running the fused slot program as one ``pl.pallas_call``
    over an ``(N, n)`` batch: ``(N, bl)`` tiles, so a block's last two
    dimensions are the whole batch and a lane-aligned (or whole) vector
    window. Integer programs compute in int32 whatever their element
    width (the TPU's vector unit has no 8-bit arithmetic) and narrow on
    store. Everything shape- or structure-dependent is closed over, so
    the callable is jit-cacheable by identity (:class:`KernelCache`)."""
    integer = jnp.issubdtype(dt, jnp.integer)
    kernel = functools.partial(
        _fused_kernel, program=program, in_slots=in_slots,
        out_slots=out_slots, n_slots=n_slots,
        bits=8 * jnp.dtype(dt).itemsize,
        wide=jnp.int32 if integer else dt)
    spec = pl.BlockSpec((N, bl), lambda i: (0, i))

    def call(*arrs):
        return pl.pallas_call(
            kernel,
            grid=(n // bl,),
            in_specs=[spec for _ in arrs],
            out_specs=[spec for _ in out_slots],
            out_shape=[jax.ShapeDtypeStruct((N, n), dt)
                       for _ in out_slots],
            interpret=interpret_mode(),
        )(*arrs)

    return _named(call, host.REGION_SCOPE)


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under the function name ``name``. A jitted kernel traced
    into the compiled walk is inlined under its function's name, so this
    is the name its custom call carries in the HLO and the device trace
    (``kvi.reduce.12``), as the named scope around it names its other
    ops."""
    def named(*args):
        return fn(*args)
    named.__name__ = named.__qualname__ = name
    return named


def fused_elementwise_call(program: Sequence[SlotOp],
                           inputs: Sequence[Tuple[int, jax.Array]],
                           out_slots: Sequence[int],
                           n_slots: Optional[int] = None,
                           block: int = 1024,
                           batched: bool = False,
                           cache: Optional[KernelCache] = None,
                           ) -> List[jax.Array]:
    """Run an element-wise slot program as one fused ``pl.pallas_call``.

    ``inputs`` preload (slot, vector) pairs; every entry of ``out_slots``
    comes back as an array of the common vector length. All vectors share
    one length and dtype (one SPM line width per program).

    With ``batched=True`` every input is ``(N, n)`` — N program instances
    — and the call covers the whole batch: one compile and ONE dispatch.
    Outputs come back ``(N, n)``.

    With a :class:`KernelCache` the call goes through a jitted compiled
    executable cached on the program's structure and shapes — repeated
    calls with the same structure (any data) skip tracing and compilation
    entirely. Two calls only differ in dispatch cost; values are
    identical either way.
    """
    program = tuple(program)
    for op, *_ in program:
        if KviOp(op) not in ELEMWISE_OPS:
            raise ValueError(f"{op} is not an element-wise KVI op")
    if not inputs:
        raise ValueError("fused program needs at least one input vector")
    if n_slots is None:
        n_slots = 1 + max([s for s, _ in inputs] + [o[1] for o in program]
                          + list(out_slots))
    if batched:
        arrs = [x.reshape(x.shape[0], -1) for _, x in inputs]
    else:
        arrs = [jnp.ravel(x)[None] for _, x in inputs]
    N, n = arrs[0].shape
    dt = arrs[0].dtype
    if any(x.shape[-1] != n for x in arrs):
        raise ValueError("input length mismatch in fused program")
    bl = pick_block(n, block)

    in_slots = tuple(s for s, _ in inputs)
    out_slots = tuple(out_slots)
    if cache is None:
        outs = _make_fused_caller(program, in_slots, out_slots, n_slots,
                                  N, n, bl, dt)(*arrs)
    else:
        key = ("fused", program, in_slots, out_slots, n_slots, N, n, bl,
               str(dt))
        fn = cache.get(key, lambda: jax.jit(_make_fused_caller(
            program, in_slots, out_slots, n_slots, N, n, bl, dt)))
        outs = fn(*arrs)
    return list(outs) if batched else [o[0] for o in outs]


# ---------------------------------------------------------------------------
# Whole-program executor: walks a KviProgram, executing the planned
# FusedRegions. The walk is batched: the register file and main memory
# carry a leading batch dimension of N program instances sharing one
# structure. The whole walk is traced into ONE jitted function per
# (structure, N), compiled once and called once per batch.
# ---------------------------------------------------------------------------

# a slot key: one (vreg id, element offset, length) window
_Key = Tuple[int, int, int]


@dataclass
class CompiledWalk:
    """One batched walk, compiled: ``fn(*staged)`` takes one ``(N, cols)``
    array per entry of ``loads`` (its dtype, and the buffers read before
    any store, concatenated in that order) and returns the stored output
    buffers, concatenated per dtype; ``outputs`` maps each such buffer to
    its ``(array, start, stop)`` columns. So a call is one host->device
    transfer per dtype and one fetch. ``fused_calls`` / ``reduce_calls``
    are the ``pallas_call``s one call issues. All but ``fn`` are filled
    in while the walk is traced."""

    loads: Tuple[Tuple[np.dtype, Tuple[int, ...]], ...]
    outputs: Dict[int, Tuple[int, int, int]] = field(default_factory=dict)
    fused_calls: int = 0
    reduce_calls: int = 0
    fn: Optional[Callable] = None


def _columns(widths: Sequence[Tuple[int, int]]):
    """``(id, width)`` pairs laid side by side: id -> (start, stop)."""
    out, start = {}, 0
    for mid, width in widths:
        out[mid] = (start, start + width)
        start += width
    return out


@register_backend("pallas")
class PallasBackend(BackendBase):
    """Executes KVI workloads on fused Pallas kernels: compiled on a TPU,
    interpreted on the CPU (:func:`~repro.kernels.common.interpret_mode`).

    max_fused_ops / max_fused_inputs bound how much of the element-wise
    subgraph one ``pallas_call`` swallows (VMEM slot-file pressure);
    programs optimized by the default pipeline arrive with a
    :class:`FusionPlan` under the same bounds, which is executed as-is.
    ``fused_calls`` counts issued ``pallas_call``s — a batch of N
    homogeneous instances issues the same number as a single instance.

    Each batch runs as one :class:`CompiledWalk` held in an
    instance-scoped :class:`KernelCache` (pass ``kernel_cache=`` to share
    one across backends), keyed on the program structure, N, the fusion
    bounds and the block: the walk's regions and reductions are nested
    calls of the cache's per-kernel jitted callables, traced into one
    device program. Repeated ``run_workload`` calls over the same
    structures and batch sizes — serving traffic, warm-up iterations,
    repeated DSE measurement classes — recompile nothing. Per-call
    hit/miss deltas land in the result's ``meta['compile_cache']``."""

    def __init__(self, block: int = 1024, max_fused_ops: int = MAX_FUSED_OPS,
                 max_fused_inputs: int = MAX_FUSED_INPUTS,
                 passes=None, verify: bool = False,
                 kernel_cache: Optional[KernelCache] = None, obs=None):
        self.block = block
        self.max_fused_ops = max_fused_ops
        self.max_fused_inputs = max_fused_inputs
        self.passes = passes
        self.verify = verify
        # optional telemetry bundle (repro.kvi.obs.Obs): run, dispatch
        # and compile-cache counters (wall time is in the profiler's
        # trace, under the kvi.* host spans)
        self.obs = obs
        self.kernel_cache = kernel_cache if kernel_cache is not None \
            else KernelCache()
        self.fused_calls = 0             # observability: pallas_call count
        self.reduce_calls = 0           # batched reduction kernel launches
        self.host_syncs = 0             # device->host fetches, one per walk
        self.walks_built = 0            # batched walks traced and compiled

    # -- register-file helpers -------------------------------------------
    # regfile[rid] is (N, length): N batched program instances.
    def _slice(self, regfile, key: _Key):
        rid, off, n = key
        r = regfile[rid]
        return jax.lax.slice(r, (0, off), (r.shape[0], off + n))

    def _set(self, regfile, key: _Key, val):
        rid, off, _ = key
        regfile[rid] = jax.lax.dynamic_update_slice(
            regfile[rid], val.astype(regfile[rid].dtype), (0, off))

    # -- fusion plan -------------------------------------------------------
    def _plan(self, program: KviProgram) -> FusionPlan:
        """The program's attached fusion plan, or a fresh one when absent
        (``passes=()``) / planned under different slot-file bounds."""
        plan = program.meta.get(META_KEY)
        if (isinstance(plan, FusionPlan)
                and plan.max_ops == self.max_fused_ops
                and plan.max_inputs == self.max_fused_inputs):
            return plan
        return plan_fusion_regions(program, self.max_fused_ops,
                                   self.max_fused_inputs)

    def _run_region(self, region: FusedRegion, regfile):
        """One planned region = ONE fused ``pallas_call`` over the whole
        batch grid."""
        inputs = [(slot, self._slice(regfile, key))
                  for key, slot in region.inputs]
        outs = fused_elementwise_call(
            region.ops, inputs, [slot for _, slot in region.outputs],
            n_slots=region.n_slots, block=self.block, batched=True,
            cache=self.kernel_cache)
        for (key, _slot), v in zip(region.outputs, outs):
            self._set(regfile, key, v)

    # -- scalar reductions -------------------------------------------------
    @staticmethod
    def _make_reducer(op: KviOp, scalar: int) -> Callable:
        """A jit-cacheable batched reduction: one kernel launch reduces
        every row (scalar immediates are baked in — they are part of the
        cache key)."""
        if op in (KviOp.KVRED, KviOp.KDOTP):
            return reduce_rows
        if op is KviOp.KDOTPPS:
            return lambda x, y: reduce_rows(x, y, shift=scalar)
        if op is KviOp.KSVADDRF:
            return lambda x: reduce_rows(x) + jnp.asarray(scalar, jnp.int32)
        if op is KviOp.KSVMULRF:
            # sum(a * s) == s * sum(a)  (mod 2^32 wrap arithmetic)
            return lambda x: reduce_rows(x) * jnp.asarray(scalar, jnp.int32)
        raise ValueError(op)             # pragma: no cover

    def _reduce(self, i: KviInstr, regfile):
        """One batched reduction kernel over the whole batch (one launch
        for N instances, traced once per structure via the kernel
        cache)."""
        a = self._slice(regfile, (i.src1.id, i.src1.offset, i.length))
        key = ("red", i.op.value, i.scalar, a.shape[0], i.length,
               str(a.dtype))
        fn = self.kernel_cache.get(
            key, lambda: jax.jit(_named(self._make_reducer(i.op, i.scalar),
                                        host.REDUCE_SCOPE)))
        if i.op in (KviOp.KDOTP, KviOp.KDOTPPS):
            b = self._slice(regfile, (i.src2.id, i.src2.offset, i.length))
            r = fn(a, b)
        else:
            r = fn(a)
        self._set(regfile, (i.dst.id, i.dst.offset, 1),
                  jnp.reshape(r, (r.shape[0], 1)))

    # -- batched walk ------------------------------------------------------
    def _walk_fn(self, proto: KviProgram, N: int):
        """The batched walk of ``proto``'s structure over N instances as
        one traceable function: every planned region is one
        ``pallas_call`` over the batch grid, every reduction one batched
        kernel, ``kmemld``/``kvcp`` register-file updates, and
        ``kmemstr`` replaces a buffer's traced contents.

        Returns ``(walk, args, spec)``: the function, the shapes of its
        staged arguments, and the :class:`CompiledWalk` it fills in as it
        is traced (all but ``fn``)."""
        plan = self._plan(proto)
        region_at = {r.items[0]: r for r in plan.regions}
        fused = plan.member_items()
        instrs = [(idx, it) for idx, it in enumerate(proto.items)
                  if not isinstance(it, ScalarBlock)]
        # the buffers read before any store: the walk's arguments, one
        # staged array per dtype
        loads: Dict[np.dtype, List[int]] = {}
        seen, stored = set(), set()
        for _, i in instrs:
            if i.op is KviOp.KMEMLD and i.src1.id not in seen:
                seen.add(i.src1.id)
                dt = jax.dtypes.canonicalize_dtype(
                    np.asarray(proto.mem_init[i.src1.id]).dtype)
                loads.setdefault(dt, []).append(i.src1.id)
            elif i.op is KviOp.KMEMSTR:
                seen.add(i.dst.id)
                stored.add(i.dst.id)
        out_ids = [m.id for m in proto.outputs if m.id in stored]
        spec = CompiledWalk(tuple((dt, tuple(mids))
                                  for dt, mids in loads.items()))
        in_cols = [_columns([(mid, proto.mem_init[mid].size)
                             for mid in mids]) for mids in loads.values()]

        def walk(*staged):
            mem = {mid: jax.lax.slice(x, (0, c0), (N, c1))
                   for x, cols in zip(staged, in_cols)
                   for mid, (c0, c1) in cols.items()}
            regfile = {r.id: jnp.zeros((N, r.length), np_dtype(r.elem_bytes))
                       for r in proto.vregs}
            for idx, i in instrs:
                region = region_at.get(idx)
                if region is not None:
                    with jax.named_scope(host.REGION_SCOPE):
                        self._run_region(region, regfile)
                    spec.fused_calls += 1
                elif idx in fused:
                    continue             # executed with its region head
                elif i.op is KviOp.KMEMLD:
                    arr = mem[i.src1.id]
                    # Mfu semantics: the whole buffer lands in the
                    # scratchpad
                    self._set(regfile,
                              (i.dst.id, i.dst.offset, arr.shape[1]),
                              arr.astype(np_dtype(i.elem_bytes)))
                elif i.op is KviOp.KMEMSTR:
                    mem[i.dst.id] = self._slice(
                        regfile, (i.src1.id, i.src1.offset, i.length))
                elif i.op is KviOp.KVCP:
                    v = self._slice(regfile,
                                    (i.src1.id, i.src1.offset, i.length))
                    self._set(regfile, (i.dst.id, i.dst.offset, i.length),
                              v)
                else:
                    with jax.named_scope(host.REDUCE_SCOPE):
                        self._reduce(i, regfile)
                    spec.reduce_calls += 1
            # one array per dtype, so the host fetches the walk at once
            by_dtype: Dict[str, List[int]] = {}
            for mid in out_ids:
                by_dtype.setdefault(str(mem[mid].dtype), []).append(mid)
            outs = []
            for k, mids in enumerate(by_dtype.values()):
                cols = _columns([(mid, mem[mid].shape[1]) for mid in mids])
                spec.outputs.update((mid, (k, c0, c1))
                                    for mid, (c0, c1) in cols.items())
                outs.append(jnp.concatenate([mem[mid] for mid in mids],
                                            axis=1))
            return tuple(outs)

        args = [jax.ShapeDtypeStruct(
            (N, sum(proto.mem_init[mid].size for mid in mids)), dt)
            for dt, mids in loads.items()]
        return walk, args, spec

    def _build_walk(self, proto: KviProgram, N: int) -> CompiledWalk:
        """Trace and compile the batched walk (:meth:`_walk_fn`)."""
        walk, args, spec = self._walk_fn(proto, N)
        spec.fn = jax.jit(walk).lower(*args).compile()
        self.walks_built += 1
        return spec

    def _run_batch(self, programs: Sequence[KviProgram], signature: tuple
                   ) -> List[Dict[str, np.ndarray]]:
        """Execute N structurally identical programs (different data) in
        one call of their compiled walk, built on the first batch of
        this structure and N (``signature`` is the group's
        :func:`~repro.kvi.workload.structural_signature`)."""
        proto = programs[0]
        N = len(programs)

        def build() -> CompiledWalk:
            with host_span(host.WALK_BUILD, N=N):
                return self._build_walk(proto, N)
        walk = self.kernel_cache.get(
            ("walk", signature, N, self.max_fused_ops,
             self.max_fused_inputs, self.block), build)
        with host_span(host.WALK_STAGE):
            staged = [np.stack([np.concatenate(
                [np.asarray(p.mem_init[mid]).reshape(-1) for mid in mids])
                for p in programs]).astype(dt, copy=False)
                for dt, mids in walk.loads]
        with host_span(host.WALK_CALL):
            outs = walk.fn(*staged)
        with host_span(host.WALK_SYNC):
            outs = jax.device_get(outs)
        self.host_syncs += 1
        self.fused_calls += walk.fused_calls
        self.reduce_calls += walk.reduce_calls

        results = []
        with host_span(host.WALK_OUTPUTS):
            for b, p in enumerate(programs):
                outputs = {}
                for m in p.outputs:
                    shape = p.mem_init[m.id].shape
                    at = walk.outputs.get(m.id)
                    # an output the walk never stores keeps its contents
                    v = p.mem_init[m.id] if at is None \
                        else outs[at[0]][b, at[1]:at[2]]
                    outputs[m.name] = np.asarray(v).reshape(shape).copy()
                results.append(outputs)
        return results

    def run_workload(self, workload: KviWorkload,
                     verify: Optional[bool] = None) -> WorkloadResult:
        """Group entries by program structure; each group runs as one
        call of its compiled batched walk (one compile per structure and
        batch size, one dispatch and one device->host fetch per group).
        Hart assignments carry no timing meaning here — on TPU the batch
        grid IS the hart-level parallelism.

        ``meta`` reports the run's observability: structural ``groups``,
        the ``walks`` run and the ``walks_built`` (traced and compiled)
        in this call, the ``pallas_calls`` those walks issued and the
        ``reductions`` among them (batched reduction kernels),
        ``host_syncs`` (device->host fetches, one per walk), this call's
        kernel-cache hit/miss deltas (``compile_cache``: a warm walk is
        one hit) and ``wall_s`` — the real execution walltime (outputs
        are materialized to numpy inside the walk, so the clock covers
        compile + dispatch + compute, not an async handle). The DSE
        walltime axis and the serving engine read these directly.
        Inside a profiler session the call, its preparation and each
        walk's build, staging, call, fetch and outputs are ``kvi.*``
        host spans (:mod:`repro.kvi.obs.host`); inside the compiled walk
        each region and reduction is under a ``kvi.region`` or
        ``kvi.reduce`` named scope, which names its device ops."""
        t0 = time.perf_counter()
        with host_span(host.RUN_WORKLOAD,
                       entries=len(workload.entries)) as span:
            with host_span(host.PREPARE):
                workload = self.optimize_workload(workload, verify=verify)
                groups: Dict[tuple, List[int]] = {}
                for idx, e in enumerate(workload.entries):
                    groups.setdefault(structural_signature(e.program),
                                      []).append(idx)
            span.set_metadata(groups=len(groups))
            calls0 = self.fused_calls + self.reduce_calls
            reductions0 = self.reduce_calls
            syncs0, built0 = self.host_syncs, self.walks_built
            cc0 = (self.kernel_cache.hits, self.kernel_cache.misses)
            entry_outputs: List[Optional[Dict[str, np.ndarray]]] = \
                [None] * len(workload.entries)
            for signature, idxs in groups.items():
                with host_span(host.WALK, N=len(idxs),
                               workload=workload.name):
                    outs = self._run_batch(
                        [workload.entries[i].program for i in idxs],
                        signature)
                for i, out in zip(idxs, outs):
                    entry_outputs[i] = out
            results = tuple(BackendResult(self.name, out)
                            for out in entry_outputs)
        calls = self.fused_calls + self.reduce_calls - calls0
        reductions = self.reduce_calls - reductions0
        built = self.walks_built - built0
        cc = {"hits": self.kernel_cache.hits - cc0[0],
              "misses": self.kernel_cache.misses - cc0[1]}
        wall_s = round(time.perf_counter() - t0, 6)
        if self.obs is not None and self.obs.enabled:
            m = self.obs.metrics
            m.counter("pallas.runs").inc()
            m.counter("pallas.calls").inc(calls)
            m.counter("pallas.reductions").inc(reductions)
            m.counter("pallas.walks").inc(len(groups))
            m.counter("pallas.walks_built").inc(built)
            m.absorb("pallas.compile_cache", cc)
        return WorkloadResult(
            self.name, workload, results,
            meta={"groups": len(groups),
                  "walks": len(groups),
                  "walks_built": built,
                  "pallas_calls": calls,
                  "reductions": reductions,
                  "host_syncs": self.host_syncs - syncs0,
                  "compile_cache": cc,
                  "wall_s": wall_s})
