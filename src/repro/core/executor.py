"""Plan execution — the JAX analogue of the paper's code generator (§6.2).

Every strategy is a *thin interpreter* over the lowered physical IR built by
:mod:`repro.core.lower` (DESIGN.md §2): one shared continuation-passing walker
(:func:`walk_ir`) folds the op sequence, and the strategies differ only in the
primitive each op maps to:

  * ``frontier`` — bottom-up fully pipelined execution, TPU-native: each HopOp
    runs the Pallas hop kernel (:mod:`repro.kernels.fragment_spmv`; compiled
    on TPU, interpret mode on CPU) over dense per-entity-domain vectors and
    the hop's destination-sorted edges (``HopOp.pull``: the index keyed on
    the destination). Bit-packed columns of the device column store
    (:mod:`repro.storage`) decode block-at-a-time in VMEM inside the kernel
    (the paper's compression-inside-the-operator design). JAX tracing fuses
    the whole plan into one XLA executable; intermediates are vectors, never
    materialized join tables.
  * ``fragment_loop`` — paper-faithful port of the generated C++ (Fig. 3):
    nested ``lax.fori_loop``s walk one fragment at a time, scalar accumulator
    updates. The §Perf baseline demonstrating why the vectorized rewrite is
    needed on TPU.
  * distributed variant — edge-sharded shard_map with one collective per hop
    (the paper's multi-thread shared-accumulator design, contention-free).

Aggregation semantics are pluggable (DESIGN.md §3): the walker is parameterized
by a :class:`repro.core.semiring.Semiring`, so SUM/COUNT, MIN/MAX, EXISTS and
the fused AVG pair all execute through the same code path in every strategy.
All strategies return the dense γ accumulator ℛ over the group-by entity domain
(the paper's aggregation array; size = domain of the group key).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import active as active_meta
from ..kernels.params import EDGE_BLOCK, LANES
from ..obs import trace as obs_trace
from ..robust.errors import ExecutionError, ValidationError
from ..robust.runner import check_deadline
from ..storage import (
    DenseColumn,
    DeviceColumn,
    DictPackedColumn,
    PackedColumn,
    build_device_column,
    column_uniques,
    resolve_device_encoding,
)
from .algebra import ChainPlan, EntityStep, Param, RelHop, SeedIds
from .fragments import FragmentIndex
from .lower import (
    DegreeFilterOp,
    EntityFilterOp,
    FusedHopOp,
    GroupOp,
    HopOp,
    LBin,
    LCall,
    LCol,
    LParam,
    PhysicalPlan,
    SeedOp,
    batch_dependent,
    eval_lexpr,
    iter_flat_ops,
    lower,
)
from .schema import Schema
from .semiring import BOOL_OR_AND, Semiring, semiring_for


@dataclass
class DeviceIndex:
    """Device-resident form of one FragmentIndex: CSR structure arrays plus
    the co-stored columns as :class:`repro.storage.DeviceColumn`s, so whether
    a column lives decoded (int32/float32 CSR) or bit-packed (BCA words /
    dictionary-packed) is a per-column physical property. ``dst_ids`` /
    ``measures`` decode on demand — the compatibility surface for consumers
    without a packed path (free when the column is dense)."""

    indptr: jnp.ndarray  # int32[h+1]
    src_ids: jnp.ndarray  # int32[E]  (CSR row ids expanded; sorted)
    dst_col: DeviceColumn  # int32[E] decoded view
    degrees: jnp.ndarray | None = None
    measure_cols: dict[str, DeviceColumn] = field(default_factory=dict)
    # per-EDGE_BLOCK [src_min, src_max] over the CSR-ordered edge arrays
    # (kernels/active.py) — the frontier-sparsity block-skipping metadata;
    # None (e.g. shard-built indexes) disables skipping for this index
    block_src_min: np.ndarray | None = None
    block_src_max: np.ndarray | None = None
    # per 128-edge row [min, max] of the dst column in CSR order (host
    # numpy, kernels/active.row_ranges): the source-chunk ranges the hop
    # kernel walks when this index serves the opposite hop as its
    # destination-sorted stream — the hop work counters' geometry
    row_dst_min: np.ndarray | None = None
    row_dst_max: np.ndarray | None = None

    @property
    def dst_ids(self) -> jnp.ndarray:
        return self.dst_col.materialize()

    @property
    def measures(self) -> dict[str, jnp.ndarray]:
        return {m: c.materialize() for m, c in self.measure_cols.items()}


@dataclass
class DeviceDB:
    schema: Schema
    indexes: dict[tuple[str, str], DeviceIndex]
    entity_attrs: dict[tuple[str, str], jnp.ndarray]
    host_indexes: dict[tuple[str, str], FragmentIndex]

    def index(self, table: str, key: str) -> DeviceIndex:
        return self.indexes[(table, key)]


def build_device_db(
    schema: Schema,
    host_indexes: dict[tuple[str, str], FragmentIndex],
    device_encodings: str | dict | None = "auto",
) -> DeviceDB:
    """Ship every fragment index to device under the storage policy.

    ``device_encodings``: ``"auto"`` (§5-style chooser, the default) |
    ``"dense"`` (decoded-CSR baseline) | ``"packed"`` (force BCA wherever it
    fits) | a per-column dict ``{(table, key, column): encoding}`` with
    ``"auto"`` filling unspecified columns. Every key of a per-column dict
    must name a real (table, key, column) address — a typo'd override would
    otherwise be silently ignored."""
    dev: dict[tuple[str, str], DeviceIndex] = {}
    seen_addrs: set[tuple[str, str, str]] = set()
    for (table, key), idx in host_indexes.items():
        other = next(c for c in idx.columns if c != key and _is_fk(schema, table, c))
        cf = idx.columns[other]
        seen_addrs.add((table, key, other))
        enc = resolve_device_encoding(
            device_encodings, (table, key, other), cf.values, cf.domain, is_key=True
        )
        src = idx.src_ids()
        bmin, bmax = active_meta.block_ranges(src)
        rmin, rmax = active_meta.row_ranges(cf.values)
        di = DeviceIndex(
            indptr=jnp.asarray(idx.indptr, dtype=jnp.int32),
            src_ids=jnp.asarray(src, dtype=jnp.int32),
            dst_col=build_device_column(cf, enc, jnp.int32),
            degrees=jnp.asarray(np.diff(idx.indptr), dtype=jnp.int32),
            block_src_min=bmin,
            block_src_max=bmax,
            row_dst_min=rmin,
            row_dst_max=rmax,
        )
        for m, cf in idx.columns.items():
            if m == other:
                continue
            seen_addrs.add((table, key, m))
            uq = column_uniques(cf.values)  # one scan shared by chooser+builder
            enc = resolve_device_encoding(
                device_encodings, (table, key, m), cf.values, cf.domain,
                is_key=False, uniques=uq,
            )
            di.measure_cols[m] = build_device_column(cf, enc, jnp.float32, uniques=uq)
        dev[(table, key)] = di
    if isinstance(device_encodings, dict):
        unknown = set(device_encodings) - seen_addrs
        if unknown:
            raise ValidationError(
                f"device_encodings keys match no index column: {sorted(unknown)}; "
                f"valid addresses: {sorted(seen_addrs)}",
                unknown=sorted(unknown),
            )
    attrs = {
        (e.name, a): jnp.asarray(col, dtype=jnp.float32)
        for e in schema.entities.values()
        for a, col in e.attributes.items()
    }
    return DeviceDB(schema, dev, attrs, host_indexes)


def _is_fk(schema: Schema, table: str, attr: str) -> bool:
    rel = schema.relationships[table]
    return attr in (rel.fk1, rel.fk2)


# ---------------------------------------------------------------------------
# Parameter handling
# ---------------------------------------------------------------------------


def collect_params(plan: ChainPlan) -> list[str]:
    names: list[str] = []

    def add(v):
        if isinstance(v, Param) and v.name not in names:
            names.append(v.name)

    def walk(p: ChainPlan):
        if isinstance(p.seed, SeedIds):
            ids = p.seed.ids if isinstance(p.seed.ids, list) else [p.seed.ids]
            for i in ids:
                add(i)
        else:
            for c in p.seed.chains:
                walk(c)
            for cc in p.seed.entity_conds:
                add(cc.value)
        for s in p.steps:
            if isinstance(s, EntityStep):
                for cc in s.conds:
                    add(cc.value)

    walk(plan)
    return names


def ensure_lowered(db: DeviceDB, plan: ChainPlan | PhysicalPlan) -> PhysicalPlan:
    return plan if isinstance(plan, PhysicalPlan) else lower(db, plan)


def densify_plan(phys: PhysicalPlan) -> PhysicalPlan:
    """Materialize every packed column bound in the IR, once, producing an
    all-dense twin of the plan. The correctness fallback for strategies with
    no packed execution path (DESIGN.md §Storage): fragment_loop's scalar
    loops index columns element-wise, so they pay one whole-column decode per
    prepare here instead of a decode per loop iteration inside the trace."""

    def dcol(col: DeviceColumn) -> DeviceColumn:
        return col if isinstance(col, DenseColumn) else DenseColumn(col.materialize())

    def dexpr(e):
        if isinstance(e, LCol) and not isinstance(e.col, DenseColumn):
            return LCol(e.key, dcol(e.col))
        if isinstance(e, LBin):
            return LBin(e.op, dexpr(e.left), dexpr(e.right))
        if isinstance(e, LCall):
            return LCall(e.fn, tuple(dexpr(a) for a in e.args))
        return e

    def dop(op):
        if isinstance(op, HopOp):
            return dataclasses.replace(
                op, dst_col=dcol(op.dst_col),
                measure=dexpr(op.measure) if op.measure is not None else None,
            )
        if isinstance(op, SeedOp) and op.programs:
            return dataclasses.replace(
                op, programs=tuple(densify_plan(p) for p in op.programs)
            )
        if isinstance(op, EntityFilterOp) and op.factor is not None:
            return dataclasses.replace(op, factor=dexpr(op.factor))
        if isinstance(op, FusedHopOp):
            return dataclasses.replace(
                op, members=tuple(dop(m) for m in op.members)
            )
        return op

    new_ops = [dop(op) for op in phys.ops]
    return PhysicalPlan(
        tuple(new_ops), phys.param_names, phys.agg, phys.out_dom, phys.source
    )


# ---------------------------------------------------------------------------
# The shared lowered-IR walker
# ---------------------------------------------------------------------------


def _trace_clean() -> bool:
    """True outside any jax trace — the guard that keeps span recording and
    ``block_until_ready`` fencing strictly on the host side of jit."""
    return jax.core.trace_ctx.is_top_level()


def walk_ir(phys: PhysicalPlan, interp: "_Interp", stop: int | None = None):
    """Fold the op sequence through ``interp``. Continuation-passing so the
    scalar strategy can emit its nested fragment loops from the same walk.

    ``stop`` truncates the walk to the first ``stop`` ops and returns the raw
    interpreter state (no finalize) — the profiling prefix entry.

    When an observability tracer is recording (``obs.trace``) *and* the walk
    runs eagerly (outside any jit trace), every op is wrapped in a nested span
    carrying its label, fenced own-time, and hop metadata — the per-op
    breakdown behind ``PreparedQuery.profile()``. Under a trace (the normal
    compiled path) the walk is the plain fold: spans record around traced
    calls, never inside them."""
    ops = phys.ops if stop is None else phys.ops[:stop]
    if obs_trace.current() is not None:
        return _walk_ir_recorded(phys, ops, interp)

    def go(i: int, state):
        if i == len(ops):
            return state
        return interp.apply(ops[i], state, lambda st: go(i + 1, st))

    return go(0, None)


def _annotate_op_span(sp, op, state, interp) -> None:
    """Static + observed metadata for one op span: shapes, strategy knobs, and
    — for a HopOp with a concrete incoming frontier — the observed support and
    surviving-block count (kernels/active.py metadata, computed on host). A
    FusedHopOp region reports ONE span annotated with its member ops (the
    region executes as one kernel pass), carrying the first hop's frontier
    metadata — the analogue of fragment_loop's "(fused into enclosing op)"
    convention."""
    if isinstance(op, FusedHopOp):
        sp.annotate(
            fused=True,
            members=[
                f"Hop({m.table}.{m.src_key}->{m.dst_entity})"
                if isinstance(m, HopOp) else type(m).__name__
                for m in op.members
            ],
        )
        _annotate_op_span(sp, op.hops[0], state, interp)
        return
    if not isinstance(op, HopOp):
        return
    sp.annotate(
        table=op.table, src_key=op.src_key,
        E=int(op.src_ids.shape[0]), dom_dst=int(op.dom_dst),
        block_skipping=getattr(interp, "block_skipping", None),
    )
    w = state
    if w is None or not hasattr(w, "shape") or isinstance(w, jax.core.Tracer):
        return
    try:
        zero = interp.sr.zero
        sup = np.asarray(w != zero)
        if sup.ndim == 2:
            sup = sup.any(axis=0)
        h = int(op.indptr.shape[0]) - 1
        if sup.ndim != 1 or sup.shape[0] != h:
            return
        degrees = np.diff(np.asarray(op.indptr))
        touched = int(degrees[sup].sum())
        E = max(int(op.src_ids.shape[0]), 1)
        sp.annotate(
            frontier_nnz=int(sup.sum()),
            observed_active_fraction=round(touched / E, 6),
        )
        if op.block_src_min is not None:
            _, na, bf = active_meta.active_block_list_np(
                sup, op.block_src_min, op.block_src_max
            )
            sp.annotate(
                active_blocks=int(na[0]),
                n_blocks=int(np.asarray(op.block_src_min).shape[0]),
                active_block_fraction=round(float(bf), 6),
            )
    except Exception:  # annotation must never break execution
        pass


def _walk_ir_recorded(phys: PhysicalPlan, ops, interp: "_Interp"):
    """The instrumented fold: one span per op, nested along the continuation
    chain (op k's span contains ops k+1..n — self time = wall − children).
    The span's ``kernel_ms`` is the ``block_until_ready``-fenced time from op
    entry to the op's own output being device-ready (captured the first time
    the continuation runs eagerly). Ops whose continuation only ever fires
    under a trace (the scalar strategy's fori_loop bodies) are closed after
    ``apply`` returns and flagged ``fused_tail`` — their time includes the
    traced downstream ops, which get no spans of their own."""
    labels = phys.op_signature()
    plan_key = id(phys.ops)

    def go(i: int, state):
        if i == len(ops):
            return state
        op = ops[i]
        if not _trace_clean():
            return interp.apply(op, state, lambda st: go(i + 1, st))
        check_deadline(labels[i])
        with obs_trace.span(labels[i], op_index=i, plan=plan_key) as sp:
            if state is not None:
                jax.block_until_ready(state)
            _annotate_op_span(sp, op, state, interp)
            t0 = time.perf_counter()
            seen = [0]

            def cont(st):
                if _trace_clean():
                    seen[0] += 1
                    if seen[0] == 1:
                        sp.annotate(
                            dispatch_ms=round((time.perf_counter() - t0) * 1e3, 4)
                        )
                        sp.fence(st)
                return go(i + 1, st)

            out = interp.apply(op, state, cont)
            if seen[0] == 0:  # continuation only ran inside a trace
                sp.annotate(
                    dispatch_ms=round((time.perf_counter() - t0) * 1e3, 4),
                    fused_tail=True,
                )
                sp.fence(out)
            sp.annotate(calls=max(seen[0], 1))
        return out

    return go(0, None)


def execute_ir(phys: PhysicalPlan, make_interp) -> jnp.ndarray:
    """Strategy-independent top level: pick the semiring for the plan's
    aggregate, run the walker (twice for AVG's fused SUM+COUNT pair), and
    apply the output convention."""
    sr = semiring_for(phys.agg)
    if phys.agg == "avg":
        # two walks in one traced program; XLA CSE merges everything the
        # weighted and count passes share (all hops up to the first measure)
        s = walk_ir(phys, make_interp(sr, True))
        c = walk_ir(phys, make_interp(sr, False))
        return jnp.where(c > 0, s / c, 0.0)
    return sr.finalize(walk_ir(phys, make_interp(sr, True)))


@dataclass(frozen=True)
class HopSlot:
    """Static layout of one counted hop in an executable's counter array:
    its label, batch groups, the frontier's chunk count, and how many
    partial sums its gather trips take (0: gathers not counted)."""

    label: str
    groups: int
    chunks: int
    parts: int

    @property
    def size(self) -> int:
        return 3 + 2 * self.parts  # executed, blocks, active chunks, sums


@jax.tree_util.register_pytree_node_class
class HopCounts:
    """The hop work counters a batched executable returns beside its result:
    one flat int32 array (device-resident until :meth:`decode` fetches it)
    and the static :class:`HopSlot` layout, as a pytree whose aux data is
    the layout."""

    def __init__(self, values, slots: tuple):
        self.values = values
        self.slots = slots

    def tree_flatten(self):
        return (self.values,), self.slots

    @classmethod
    def tree_unflatten(cls, slots, children):
        return cls(children[0], slots)

    def decode(self) -> list[dict]:
        """Fetch the counters: per counted hop in trace order, its ``label``,
        ``rows`` streamed (blocks × 32 × batch groups), ``gathers`` (the
        gather-loop trips of ``hop_block``; None where not counted) and the
        frontier's ``active_chunks`` (None where not counted) of
        ``chunks``. A hop the early exit skipped counts 0 rows and gathers."""
        v = [int(x) for x in np.asarray(self.values)]
        out, i = [], 0
        for sl in self.slots:
            executed, blocks, active = v[i:i + 3]
            sums = v[i + 3:i + sl.size]
            i += sl.size
            rows = executed * blocks * (EDGE_BLOCK // LANES) * sl.groups
            gathers = None
            if sl.parts:
                trips = sum(sums[:sl.parts]) + (sum(sums[sl.parts:]) << 16)
                gathers = executed * sl.groups * trips
            out.append(dict(label=sl.label, rows=rows, gathers=gathers,
                            active_chunks=active if sl.parts else None,
                            chunks=sl.chunks))
        return out


class HopLog:
    """Numbering of an executable's hops (``h<k>`` in trace order, mask
    sub-programs included) for the kernels' labels, and — when ``counting``
    (the batched frontier executable) — the hop work counters, in-graph.
    One log is shared by an interpreter and every interpreter it spawns."""

    def __init__(self, counting: bool = False):
        self.counting = counting
        self.n = 0
        self.slots: list[HopSlot] = []
        self.values: list = []

    def label(self, op: HopOp, layout: str) -> str:
        k, self.n = self.n, self.n + 1
        return f"h{k}:{op.table}.{op.src_key}->{op.dst_entity}:{layout}"

    def add(self, label: str, w, live, blocks, flags=None, coverage=None,
            chunks: int = 0) -> None:
        """Count one hop run over frontier ``w``: ``blocks`` streamed edge
        blocks; gather trips too when ``flags`` (its active chunks) and the
        stream's ``coverage`` are given."""
        from ..kernels.fragment_spmv import GROUP_ROWS, gather_trips, group_pad

        head = [live.astype(jnp.int32), jnp.asarray(blocks, jnp.int32)]
        parts = 0
        if flags is not None and coverage is not None:
            sums = gather_trips(flags, coverage)
            parts = sums.shape[0] // 2
            vec = jnp.concatenate([jnp.stack(head + [flags.sum(dtype=jnp.int32)]), sums])
        else:
            vec = jnp.stack(head + [jnp.int32(0)])
        groups = group_pad(w.shape[0]) // GROUP_ROWS  # w is [B, n_src]
        self.slots.append(HopSlot(label, groups, chunks, parts))
        self.values.append(vec)

    def counts(self) -> HopCounts:
        vals = (jnp.concatenate(self.values) if self.values
                else jnp.zeros((0,), jnp.int32))
        return HopCounts(vals, tuple(self.slots))


class _Interp:
    """Op dispatch + parameter/seed-scalar environment shared by strategies."""

    def __init__(self, params: dict[str, Any], sr: Semiring, use_measures: bool = True):
        self.params = params
        self.sr = sr
        self.use_measures = use_measures
        self.scalars: dict[tuple, Any] = {}

    def apply(self, op, state, cont):
        if isinstance(op, SeedOp):
            return self.seed(op, state, cont)
        if isinstance(op, HopOp):
            return self.hop(op, state, cont)
        if isinstance(op, DegreeFilterOp):
            return self.degree_filter(op, state, cont)
        if isinstance(op, EntityFilterOp):
            return self.entity_filter(op, state, cont)
        if isinstance(op, GroupOp):
            return self.group(op, state, cont)
        if isinstance(op, FusedHopOp):
            return self.fused_hop(op, state, cont)
        raise ExecutionError(
            f"no interpreter rule for op {type(op).__name__}",
            retryable=False, op=type(op).__name__,
            strategy=type(self).__name__,
        )

    def fused_hop(self, op: "FusedHopOp", state, cont):
        """Default semantics of a fused region: replay its member ops through
        the ordinary per-op rules (CPS, so the scalar strategy's nested loops
        come out identical to the unfused plan). Strategies with a true
        single-pass kernel (frontier) override this."""
        members = op.members

        def go(i: int, st):
            if i == len(members):
                return cont(st)
            return self.apply(members[i], st, lambda s2: go(i + 1, s2))

        return go(0, state)

    def resolve(self, v):
        return self.params[v.name] if isinstance(v, LParam) else v

    def capture_scalars(self, op: SeedOp, sid):
        self.scalars = {
            s.key: self.attr_col(s)[sid] for s in op.scalars.values()
        }

    # column access — overridden by the distributed interpreter
    def col(self, c):
        return c.array

    def attr_col(self, c):
        return c.array


# ---------------------------------------------------------------------------
# Frontier strategy (and its edge-sharded distributed variant)
# ---------------------------------------------------------------------------


class _FrontierInterp(_Interp):
    """Dense frontier vectors; each hop is one fused gather⊗measure→scatter-⊕
    kernel call.

    Frontier sparsity (DESIGN.md §Sparsity): every hop first short-circuits an
    all-zero frontier inside the trace (``lax.cond`` on the support count — a
    died-early chain stops paying per-hop scan cost). Over the
    destination-sorted edges the kernel then gathers only active frontier
    chunks but streams every edge block; a hop without a reverse index runs
    over its source-sorted edges, where the per-block src-range metadata
    keeps unreachable blocks from being streamed. ``block_skipping`` ('auto'
    | 'on' | 'off') is threaded through from prepare time."""

    # Subclasses whose hops run collectives (the edge-sharded distributed
    # interp) must not branch per-hop: lax.cond with a psum inside one branch
    # deadlocks when shards disagree on the frontier. They opt out here.
    early_exit = True
    # Hops run the Pallas kernel over the destination-sorted edges; the
    # edge-sharded interp reduces shard-local XLA segments instead.
    kernel_hops = True
    # The edge-sharded interp also opts out of the single-pass fused-region
    # kernel (its hops are shard-local segment reduces, no VMEM pipeline) and
    # replays fused regions op-by-op via the generic rule instead.
    fuse_kernels = True

    def __init__(self, params: dict[str, Any], sr: Semiring,
                 use_measures: bool = True, block_skipping: str = "auto",
                 use_pallas: bool = True, fusion: str = "auto",
                 hops: HopLog | None = None):
        super().__init__(params, sr, use_measures)
        self.block_skipping = block_skipping
        self.use_pallas = use_pallas
        self.fusion = fusion
        self.hops = hops if hops is not None else HopLog()

    def spawn(self) -> "_FrontierInterp":
        """Interpreter for a mask sub-program (always the boolean semiring)."""
        return _FrontierInterp(
            self.params, BOOL_OR_AND, block_skipping=self.block_skipping,
            use_pallas=self.use_pallas, fusion=self.fusion, hops=self.hops,
        )

    def blocks_for(self, op: HopOp):
        """The hop's (src_min, src_max) skip metadata, or None when absent or
        skipping is off — kernel dispatch treats both as 'full scan'."""
        if self.block_skipping == "off" or op.block_src_min is None:
            return None
        return (op.block_src_min, op.block_src_max)

    def seed(self, op: SeedOp, state, cont):
        sr = self.sr
        if op.ids is not None:
            idx = jnp.asarray([self.resolve(i) for i in op.ids], dtype=jnp.int32)
            # scatter-⊕, not set: duplicate seed ids must accumulate
            # multiplicity under the sum semiring (matches the oracle and the
            # per-seed unrolling of the fragment_loop strategy)
            w = sr.scatter(jnp.full(op.dom, sr.zero, jnp.float32), idx, sr.one)
            if op.scalars:
                self.capture_scalars(op, self.resolve(op.ids[0]))
            return cont(w)
        m = jnp.ones(op.dom, jnp.float32)
        for prog in op.programs:
            m = m * walk_ir(prog, self.spawn())
        if op.const_mask is not None:
            m = m * op.const_mask
        for c in op.param_conds:
            m = m * c.mask(self.params, self.attr_col).astype(jnp.float32)
        return cont(sr.from_mask(m))

    def hop(self, op: HopOp, state, cont):
        sr, w = self.sr, state
        if op.semijoin:
            w = sr.binarize(w)
        label = self.hops.label(op, "pull" if op.pull is not None else "src")
        if not self.early_exit:
            return cont(self._hop_body(w, op, label))
        # all-zero frontier short-circuit: the hop's result is the ⊕-identity
        # accumulator whatever the index holds, so skip the kernel entirely —
        # in-trace, so multi-hop chains that die early stop scanning
        empty = jnp.count_nonzero(w != sr.zero) == 0
        if self.hops.counting:
            self._count_hop(op, w, ~empty, label)
        out_shape = w.shape[:-1] + (op.dom_dst,)
        return cont(jax.lax.cond(
            empty,
            lambda w: jnp.full(out_shape, sr.zero, jnp.float32),
            lambda w: self._hop_body(w, op, label),
            w,
        ))

    def _kernel_stream(self, op: HopOp) -> str | None:
        """Which stream the hop kernel reads for ``op`` in a batched
        executable: ``pull`` (destination-sorted), ``src`` (source-sorted),
        or None when the hop stays on XLA (the ``xla`` rung, a per-row
        measure) — the static twin of ``pull_hop``/``_hop_body``'s routing."""
        if not (self.use_pallas and self.kernel_hops):
            return None
        measure = op.pull.measure if op.pull is not None else op.measure
        if self.use_measures and measure is not None and batch_dependent(measure):
            return None
        return "pull" if op.pull is not None else "src"

    def _count_hop(self, op: HopOp, w, live, label: str) -> None:
        """The hop's work counters, outside the early-exit cond: rows
        streamed and, over the destination-sorted stream, the gather loop's
        trips for this frontier's active chunks (kernels/fragment_spmv.py
        ``hop_block``). Source-sorted hops count rows only."""
        from ..kernels import ops as K
        from ..kernels.fragment_spmv import n_chunks

        kind = self._kernel_stream(op)
        if kind == "pull":
            self._count_pull(label, w, live, op.pull)
        elif kind == "src" and op.src_ids.shape[0]:
            E = int(op.src_ids.shape[0])
            blocks = K.streamed_blocks(w, self.sr.name, E, self.blocks_for(op),
                                       self.block_skipping)
            self.hops.add(label, w, live, blocks, chunks=n_chunks(w.shape[-1]))

    def _count_pull(self, label: str, w, live, pull) -> None:
        """Counters of a hop over its destination-sorted stream ``pull``,
        which streams every edge block."""
        from ..kernels.fragment_spmv import chunk_flags, n_chunks

        E = int(pull.dst_ids.shape[0])
        if E:
            n_src = w.shape[-1]
            self.hops.add(
                label, w, live, active_meta.n_edge_blocks(E),
                chunk_flags(w, self.sr.name, self.block_skipping != "off"),
                pull.coverage(n_src), chunks=n_chunks(n_src),
            )

    def _hop_body(self, w, op: HopOp, label: str = ""):
        pulled = self.pull_hop(w, op, label)
        if pulled is not None:
            return pulled
        src, dst, valid = self.edge_arrays(op)
        E = src.shape[0]
        if op.measure is not None and self.use_measures:
            m = eval_lexpr(op.measure, self.params, self.scalars, self.col)
            m = jnp.broadcast_to(jnp.asarray(m, jnp.float32), (E,))
        else:
            m = jnp.ones(E, jnp.float32)
        return self.spmv(w, src, dst, m, valid, op, label)

    def edge_arrays(self, op: HopOp):
        return op.src_ids, op.dst_ids, None

    def _measure_layout(self, measure):
        """``(m_mode, m_operand, m_width, mdict)`` for the kernels: a single
        packed column streams its words ('packed' | 'dict'); any other
        measure is evaluated and broadcast by the caller ('dense', operand
        None)."""
        m = measure if self.use_measures else None
        if m is None:
            return "none", None, 0, None
        if isinstance(m, LCol) and isinstance(m.col, PackedColumn):
            return "packed", m.col.words, m.col.width, None
        if isinstance(m, LCol) and isinstance(m.col, DictPackedColumn):
            return "dict", m.col.words, m.col.width, m.col.dictionary
        return "dense", None, 0, None

    def _pull_operands(self, op: HopOp):
        """The hop's destination-sorted streams (``op.pull``) as keyword
        arguments of the packed hop entries — packed source ids and a single
        packed measure column decode inside the kernel — or None when the
        hop takes the source-sorted XLA path instead: the ``xla`` rung, no
        reverse index, or a batch-dependent measure (no shared edge stream;
        XLA by design)."""
        p = op.pull
        if p is None or not self.use_pallas or not self.kernel_hops:
            return None
        m_mode, m_operand, m_width, mdict = self._measure_layout(p.measure)
        E = p.dst_ids.shape[0]
        if m_mode == "dense":
            mv = jnp.asarray(
                eval_lexpr(p.measure, self.params, self.scalars, self.col),
                jnp.float32,
            )
            if mv.ndim >= 2:
                return None
            m_operand = jnp.broadcast_to(mv, (E,))
        src_packed = isinstance(p.src_col, PackedColumn)
        return dict(
            src_ids=p.src_col.words if src_packed else p.src_col.materialize(),
            dst=p.dst_ids, measure=m_operand, mdict=mdict, n_dst=op.dom_dst,
            src_width=p.src_col.width if src_packed else 0,
            m_mode=m_mode, m_width=m_width,
        )

    def pull_hop(self, w, op: HopOp, label: str = ""):
        """The hop kernel over the destination-sorted edges. Skipping there is
        by frontier chunk: only 128-entry chunks of ``w`` holding a
        non-identity value are gathered (``block_skipping`` 'off' reads every
        chunk)."""
        from ..kernels import ops as K

        kw = self._pull_operands(op)
        if kw is None:
            return None
        return K.fragment_spmv_packed(
            w, op=self.sr.name, use_pallas=True,
            block_skipping=self.block_skipping, label=label, **kw,
        )

    def spmv(self, w, src, dst, m, valid, op: HopOp, label: str = ""):
        from ..kernels import ops as K

        return K.fragment_spmv(
            w, src, dst, m, n_dst=op.dom_dst, op=self.sr.name,
            use_pallas=self.use_pallas,
            blocks=self.blocks_for(op), block_skipping=self.block_skipping,
            label=label,
        )

    # -- pipelined fused regions (DESIGN.md §Pipelined fusion) --------------

    def _hop_operands(self, op: HopOp):
        """One HopOp → the fused entry's :class:`FusedHopOperands` bundle, or
        None when the hop does not run the kernel (see ``_pull_operands``) —
        the caller then replays the region unfused."""
        from ..kernels import ops as K

        kw = self._pull_operands(op)
        return K.FusedHopOperands(**kw) if kw is not None else None

    def _fused_region_args(self, op: FusedHopOp):
        """Collect the region's kernel arguments: the two hop bundles, the
        product of the member filters' constant masks, and whether hop2's
        semijoin entry binarizes the intermediate. None ⇒ fall back to the
        generic member-replay rule."""
        hops = op.hops
        h1_op = hops[0]
        h2_op = hops[1] if len(hops) > 1 else None
        hop1 = self._hop_operands(h1_op)
        if hop1 is None:
            return None
        hop2 = None
        if h2_op is not None:
            hop2 = self._hop_operands(h2_op)
            if hop2 is None:
                return None
        mid_mask = None
        for f in op.mid_filters:
            if f.const_mask is None:
                continue
            m = jnp.asarray(f.const_mask, jnp.float32)
            mid_mask = m if mid_mask is None else mid_mask * m
        mid_binarize = bool(h2_op.semijoin) if h2_op is not None else False
        return h1_op, h2_op, hop1, hop2, mid_mask, mid_binarize

    def _fused_call(self, w, hop1, hop2, mid_mask, mid_binarize, labels):
        from ..kernels import ops as K

        return K.fragment_spmv_fused(
            w, hop1, hop2, mid_mask, op=self.sr.name,
            mid_binarize=mid_binarize, use_pallas=self.use_pallas,
            fusion=self.fusion, block_skipping=self.block_skipping,
            labels=labels,
        )

    def fused_hop(self, op: FusedHopOp, state, cont):
        """Single-pass execution of a fused region: hop1 accumulates into a
        VMEM scratch frontier, the member filters' constant mask and hop2's
        semijoin binarize apply in-register at the phase boundary, hop2
        streams against the resident intermediate. The all-zero-frontier
        short circuit wraps the whole region (one cond instead of two)."""
        if not self.fuse_kernels or self.fusion == "off":
            return super().fused_hop(op, state, cont)
        args = self._fused_region_args(op)
        if args is None:
            return super().fused_hop(op, state, cont)
        from ..kernels import ops as K

        h1_op, h2_op, hop1, hop2, mid_mask, mid_binarize = args
        sr, w = self.sr, state
        if h1_op.semijoin:
            w = sr.binarize(w)
        n_out = hop2.n_dst if hop2 is not None else hop1.n_dst
        # a fused hop2 gathers from the VMEM-resident intermediate
        fused = K.fuses(hop1, hop2, self.fusion, self.use_pallas)
        labels = (self.hops.label(h1_op, "pull"),)
        if h2_op is not None:
            labels += (self.hops.label(h2_op, "fused" if fused else "pull"),)

        def body(w):
            return self._fused_call(w, hop1, hop2, mid_mask, mid_binarize, labels)

        if not self.early_exit:
            out = body(w)
        else:
            out_shape = w.shape[:-1] + (n_out,)
            empty = jnp.count_nonzero(w != sr.zero) == 0
            if self.hops.counting:
                self._count_region(h1_op, h2_op, hop1, hop2, w, ~empty, labels)
            out = jax.lax.cond(
                empty,
                lambda w: jnp.full(out_shape, sr.zero, jnp.float32),
                body, w,
            )
        g = op.group
        if g is not None and g.entity is None:
            out = sr.to_mask(out)
        return cont(out)

    def _count_region(self, h1_op, h2_op, hop1, hop2, w, live, labels) -> None:
        """A fused region's counters: hop1 as a destination-sorted hop over
        ``w``; hop2's rows only — its frontier is the intermediate, which
        exists only inside the region (in VMEM, or inside the early-exit
        cond when the region composes the unfused kernels)."""
        from ..kernels.fragment_spmv import n_chunks

        self._count_pull(labels[0], w, live, h1_op.pull)
        if hop2 is not None and hop2.n_edges():
            self.hops.add(labels[1], w, live,
                          active_meta.n_edge_blocks(hop2.n_edges()),
                          chunks=n_chunks(hop1.n_dst))

    def degree_filter(self, op: DegreeFilterOp, state, cont):
        return cont(self.sr.mask(state, self.degrees(op) > 0))

    def degrees(self, op: DegreeFilterOp):
        return op.degrees

    def entity_filter(self, op: EntityFilterOp, state, cont):
        w = state
        if op.factor is not None and self.use_measures:
            f = eval_lexpr(op.factor, self.params, self.scalars, self.col)
            w = self.sr.extend(w, jnp.asarray(f, jnp.float32))
        if op.const_mask is not None:
            w = self.sr.mask(w, op.const_mask)
        for c in op.param_conds:
            w = self.sr.mask(w, c.mask(self.params, self.attr_col))
        return cont(w)

    def group(self, op: GroupOp, state, cont):
        if op.entity is None:
            return cont(self.sr.to_mask(state))
        return cont(state)


class hoisted_jit:
    """``jax.jit(fn)`` with every array ``fn`` closes over — the plan's edge
    columns, masks and degree vectors — passed to the executable as an
    argument. Plain ``jax.jit`` bakes closed-over arrays into the executable
    as constants: at deployment size that is hundreds of MB per executable,
    copied into host memory and through the compiler for every prepared
    shape. Traces once per argument signature; ``lower`` mirrors
    ``jax.jit(...).lower`` for executable inspection.

    The executable is named ``name`` (the XLA module ``jit_<name>``). With
    ``counts``, ``fn`` returns ``(result, HopCounts)``: calling the object
    returns the result alone, :meth:`counted` both. Building an entry —
    tracing it and its first call, which compiles — runs in a ``compile``
    span (``obs.trace``)."""

    def __init__(self, fn, name: str = "gqfast_executable", counts: bool = False):
        self.fn = fn
        self.name = name
        self.counts = counts
        self._entries: dict = {}

    def _entry(self, args):
        key = tuple(jax.typeof(a) for a in args)
        if key not in self._entries:
            closed, shape = jax.make_jaxpr(self.fn, return_shape=True)(*args)
            tree = jax.tree.structure(shape)
            consts = [jnp.asarray(c) if isinstance(c, np.ndarray) else c
                      for c in closed.consts]

            def run(consts, *a):
                return jax.tree.unflatten(
                    tree, jax.core.eval_jaxpr(closed.jaxpr, consts, *a)
                )

            run.__name__ = run.__qualname__ = self.name
            self._entries[key] = [jax.jit(run), consts, False]
        return self._entries[key]

    def _run(self, args):
        entry = self._entries.get(tuple(jax.typeof(a) for a in args))
        if entry is not None and entry[2]:
            return entry[0](entry[1], *args)
        with obs_trace.span("compile", executable=self.name):
            entry = self._entry(args)
            out = entry[0](entry[1], *args)  # lowers and compiles, then runs
        entry[2] = True
        return out

    def __call__(self, *args):
        out = self._run(args)
        return out[0] if self.counts else out

    def counted(self, *args):
        """``(result, HopCounts | None)`` from one execution."""
        out = self._run(args)
        return tuple(out) if self.counts else (out, None)

    def lower(self, *args):
        run, consts, _ = self._entry(args)
        return run.lower(consts, *args)


def compile_frontier(
    db: DeviceDB, plan: ChainPlan | PhysicalPlan,
    block_skipping: str = "auto", use_pallas: bool = True,
    fusion: str = "auto", name: str = "gqfast_frontier",
) -> Callable[..., jnp.ndarray]:
    phys = ensure_lowered(db, plan)
    names = list(phys.param_names)

    @functools.partial(hoisted_jit, name=name)
    def run(*args):
        params = dict(zip(names, args))
        return execute_ir(
            phys,
            lambda sr, um: _FrontierInterp(
                params, sr, um, block_skipping=block_skipping,
                use_pallas=use_pallas, fusion=fusion,
            ),
        )

    return run


# ---------------------------------------------------------------------------
# Batched frontier strategy (multi-query SpMM serving path)
# ---------------------------------------------------------------------------


class _BatchedFrontierInterp(_FrontierInterp):
    """Frontier semantics with a leading batch axis threaded through the
    walker state: frontiers are [B, dom] matrices and each HopOp is one fused
    SpMM pass (kernels/fragment_spmv.py) that streams the edge arrays once
    for all B queries — not a vmap of the whole plan, so the kernel sees the
    batch as a unit. Parameters arrive as [B, 1] columns (broadcast against
    per-entity [dom] and per-edge [E] arrays yields [B, ·]); seed ids reshape
    back to [B] for indexing. Per-op batching rules:

      * SeedOp        — scatter B seed ids at once (one 2-D scatter-⊕);
                        mask seeds run their sub-programs batched.
      * EntityFilter/ — masks and degree vectors are [dom] (or [B, dom] when
        DegreeFilter    parameter-dependent) and broadcast against [B, dom].
      * GroupOp       — returns the [B, dom] accumulator (or mask) as-is.
    """

    def __init__(self, params: dict[str, Any], sr: Semiring,
                 use_measures: bool = True, *, batch: int,
                 block_skipping: str = "auto", use_pallas: bool = True,
                 fusion: str = "auto", hops: HopLog | None = None):
        super().__init__(params, sr, use_measures,
                         block_skipping=block_skipping, use_pallas=use_pallas,
                         fusion=fusion, hops=hops)
        self.batch = batch

    def spawn(self) -> "_BatchedFrontierInterp":
        return _BatchedFrontierInterp(
            self.params, BOOL_OR_AND, batch=self.batch,
            block_skipping=self.block_skipping, use_pallas=self.use_pallas,
            fusion=self.fusion, hops=self.hops,
        )

    def _fused_call(self, w, hop1, hop2, mid_mask, mid_binarize, labels):
        from ..kernels import ops as K

        return K.fragment_spmm_fused(
            w, hop1, hop2, mid_mask, op=self.sr.name,
            mid_binarize=mid_binarize, use_pallas=self.use_pallas,
            fusion=self.fusion, block_skipping=self.block_skipping,
            labels=labels,
        )

    def _seed_ids(self, i) -> jnp.ndarray:
        """One seed slot → [B] int32 (constants broadcast across the batch)."""
        v = self.resolve(i)
        if isinstance(v, (int, float)):
            return jnp.full((self.batch,), int(v), jnp.int32)
        return jnp.asarray(v).reshape(-1).astype(jnp.int32)

    def capture_scalars(self, op: SeedOp, sid):
        # sid is [B]; keep scalars as [B, 1] columns so downstream expression
        # broadcasting against [dom]/[E] arrays lands on [B, ·]
        self.scalars = {
            s.key: self.attr_col(s)[sid][:, None] for s in op.scalars.values()
        }

    def seed(self, op: SeedOp, state, cont):
        sr, B = self.sr, self.batch
        if op.ids is not None:
            cols = [self._seed_ids(i) for i in op.ids]
            idx = jnp.stack(cols, axis=1)  # [B, n_ids]
            w = jnp.full((B, op.dom), sr.zero, jnp.float32)
            # scatter-⊕ per row (duplicate ids accumulate multiplicity, as in
            # the single-query path); sr.scatter takes any advanced index
            w = sr.scatter(w, (jnp.arange(B)[:, None], idx), sr.one)
            if op.scalars:
                self.capture_scalars(op, cols[0])
            return cont(w)
        m = jnp.ones((B, op.dom), jnp.float32)
        for prog in op.programs:
            m = m * walk_ir(prog, self.spawn())
        if op.const_mask is not None:
            m = m * op.const_mask
        for c in op.param_conds:
            m = m * c.mask(self.params, self.attr_col).astype(jnp.float32)
        return cont(sr.from_mask(m))

    def _hop_body(self, w, op: HopOp, label: str = ""):
        # the [B, n_src] frontier reaches the kernel dispatch whole: the
        # active-chunk list is the union of per-row supports, so one SMEM
        # list serves the entire batch
        from ..kernels import ops as K

        pulled = self.pull_hop(w, op, label)
        if pulled is not None:
            return pulled
        src, dst = op.src_ids, op.dst_ids
        E = src.shape[0]
        if op.measure is not None and self.use_measures:
            m = jnp.asarray(
                eval_lexpr(op.measure, self.params, self.scalars, self.col),
                jnp.float32,
            )
        else:
            m = jnp.ones((), jnp.float32)
        if m.ndim <= 1:  # scalar or shared per-edge stream → SpMM kernel
            m = jnp.broadcast_to(m, (E,))
        else:  # per-row measure (seed scalars / params) → [B, E], XLA fallback
            m = jnp.broadcast_to(m, (w.shape[0], E))
        return K.fragment_spmm(
            w, src, dst, m, n_dst=op.dom_dst, op=self.sr.name,
            use_pallas=self.use_pallas,
            blocks=self.blocks_for(op), block_skipping=self.block_skipping,
            label=label,
        )

    def pull_hop(self, w, op: HopOp, label: str = ""):
        from ..kernels import ops as K

        kw = self._pull_operands(op)
        if kw is None:
            return None
        return K.fragment_spmm_packed(
            w, op=self.sr.name, use_pallas=True,
            block_skipping=self.block_skipping, label=label, **kw,
        )


def compile_frontier_batched(
    db: DeviceDB, plan: ChainPlan | PhysicalPlan,
    block_skipping: str = "auto", use_pallas: bool = True,
    fusion: str = "auto", name: str = "gqfast_frontier_batched",
) -> Callable[..., jnp.ndarray]:
    """Batched serving entry: takes one [B] array per query parameter and
    returns the [B, out_dom] result block in one traced pass — every HopOp
    runs as a fused SpMM streaming the edge arrays once for the whole batch.
    Each distinct B compiles once; callers bound recompiles by padding ragged
    batches to bucket sizes (engine.PreparedQuery.execute_batch). The same
    executable also computes the hop work counters (:class:`HopCounts`),
    which ``counted`` returns beside the result and which stay on the
    device unless fetched."""
    phys = ensure_lowered(db, plan)
    names = list(phys.param_names)
    if not names:
        raise ValidationError(
            "batched execution needs at least one query parameter"
        )

    @functools.partial(hoisted_jit, name=name, counts=True)
    def run(*args):
        B = args[0].shape[0]
        params = {n: jnp.asarray(a)[:, None] for n, a in zip(names, args)}
        log = HopLog(counting=True)
        out = execute_ir(
            phys,
            lambda sr, um: _BatchedFrontierInterp(
                params, sr, um, batch=B, block_skipping=block_skipping,
                use_pallas=use_pallas, fusion=fusion, hops=log,
            ),
        )
        return out, log.counts()

    return run


# ---------------------------------------------------------------------------
# Paper-faithful fragment-at-a-time strategy (Fig. 3 port)
# ---------------------------------------------------------------------------


class _FragmentLoopInterp(_Interp):
    """Scalar state (cur_id, weight, ℛ): HopOps emit nested fori_loops over
    one fragment at a time; GroupOp is a single scalar ⊕-update per completed
    path — a direct port of the generated C++."""

    def __init__(self, params, sr, use_measures=True, out_dom: int = 0):
        super().__init__(params, sr, use_measures)
        self.out_dom = out_dom

    def seed(self, op: SeedOp, state, cont):
        sr = self.sr
        R = jnp.full(self.out_dom, sr.zero, jnp.float32)
        if op.scalars:
            self.capture_scalars(op, self.resolve(op.ids[0]))
        for i in op.ids:  # static seed count: unrolled chain per seed id
            sid = jnp.asarray(self.resolve(i), dtype=jnp.int32)
            R = cont((sid, jnp.float32(sr.one), R))
        return R

    def hop(self, op: HopOp, state, cont):
        cur, wgt, R = state
        start = op.indptr[cur]
        n = op.indptr[cur + 1] - start

        def body(k, Rc):
            e = start + k
            w2 = wgt
            if op.measure is not None and self.use_measures:
                mval = eval_lexpr(
                    op.measure, self.params, self.scalars, lambda c: c.array[e]
                )
                w2 = self.sr.extend(w2, mval)
            return cont((op.dst_ids[e], w2, Rc))

        return jax.lax.fori_loop(0, n, body, R)

    def degree_filter(self, op: DegreeFilterOp, state, cont):
        cur, wgt, R = state
        return cont((cur, self.sr.select(op.degrees[cur] > 0, wgt), R))

    def entity_filter(self, op: EntityFilterOp, state, cont):
        cur, wgt, R = state
        if op.factor is not None and self.use_measures:
            f = eval_lexpr(
                op.factor, self.params, self.scalars, lambda c: c.array[cur]
            )
            wgt = self.sr.extend(wgt, f)
        keep = None
        if op.const_mask is not None:
            keep = op.const_mask[cur] > 0
        for c in op.param_conds:
            k = c.mask(self.params, lambda cc: cc.array[cur])
            keep = k if keep is None else keep & k
        if keep is not None:
            wgt = self.sr.select(keep, wgt)
        return cont((cur, wgt, R))

    def group(self, op: GroupOp, state, cont):
        cur, wgt, R = state
        return cont(self.sr.scatter(R, cur, wgt))


def compile_fragment_loop(
    db: DeviceDB, plan: ChainPlan | PhysicalPlan,
    block_skipping: str = "auto", use_pallas: bool = True,
    name: str = "gqfast_fragment_loop",
) -> Callable[..., jnp.ndarray]:
    """Nested fori_loops over fragments, scalar per-edge accumulator updates.
    Only id-seeded chains (SD/FSD/AS shapes); mask seeds and semijoins fall
    back to the frontier strategy. ``block_skipping`` only applies to that
    fallback — the scalar loop already touches only reached fragments."""
    phys = ensure_lowered(db, plan)
    seed_op = phys.ops[0]
    if seed_op.ids is None or any(
        isinstance(op, HopOp) and op.semijoin for op in iter_flat_ops(phys)
    ):
        return compile_frontier(db, phys, block_skipping=block_skipping,
                                use_pallas=use_pallas, name=name)
    phys = densify_plan(phys)  # scalar loops have no packed path (§Storage)
    names = list(phys.param_names)

    @functools.partial(hoisted_jit, name=name)
    def run(*args):
        params = dict(zip(names, args))
        return execute_ir(
            phys,
            lambda sr, um: _FragmentLoopInterp(params, sr, um, out_dom=phys.out_dom),
        )

    return run


# ---------------------------------------------------------------------------
# Distributed (edge-sharded shard_map, one collective per hop)
# ---------------------------------------------------------------------------


def shard_edges(db: DeviceDB, mesh: Mesh, axes: tuple[str, ...]) -> DeviceDB:
    """Pad every index's edge arrays to a multiple of the shard count and place
    them edge-sharded on ``axes``; padding edges carry ``__valid__`` 0 and are
    masked to the semiring zero inside every hop.

    The shard trees are always dense. Each array is built on the host from
    the decoded host index the device columns were encoded from (so packed
    columns need no device decode), padded there, and put straight into its
    sharding: every device receives only its own slice."""
    nshards = int(np.prod([mesh.shape[a] for a in axes]))
    sharding = NamedSharding(mesh, P(axes))
    out: dict[tuple[str, str], DeviceIndex] = {}
    for (table, key), di in db.indexes.items():
        hidx = db.host_indexes[(table, key)]
        E = hidx.num_edges
        pad = (-E) % nshards

        def place(a, dtype):
            a = np.asarray(a, dtype)
            return jax.device_put(np.concatenate([a, np.zeros(pad, dtype)]), sharding)

        other = next(c for c in hidx.columns if c != key and _is_fk(db.schema, table, c))
        nd = DeviceIndex(
            indptr=di.indptr,
            src_ids=place(hidx.src_ids(), np.int32),
            dst_col=DenseColumn(place(hidx.columns[other].values, np.int32)),
            degrees=di.degrees,
        )
        nd.measure_cols = {
            m: DenseColumn(place(hidx.columns[m].values, np.float32))
            for m in di.measure_cols
        }
        nd.measure_cols["__valid__"] = DenseColumn(place(np.ones(E), np.float32))
        out[(table, key)] = nd
    return DeviceDB(db.schema, out, db.entity_attrs, db.host_indexes)


class _DistributedInterp(_FrontierInterp):
    """Frontier semantics with edge arrays drawn from the shard_map argument
    trees and one ⊕-collective per hop (psum/pmin/pmax by semiring).

    No per-hop lax.cond early exit (``early_exit = False``): each hop ends in
    a psum/pmin/pmax and a collective inside one cond branch deadlocks when
    shards disagree about the frontier. Block skipping is likewise off — the
    sharded hop is an XLA segment-reduce over shard-local padded edge arrays,
    not a Pallas block stream, so there are no blocks to skip."""

    early_exit = False
    fuse_kernels = False
    kernel_hops = False

    def __init__(self, params, sr, use_measures=True, *, edges=None, side=None,
                 axes=("data",), frontier_dtype=jnp.float32):
        super().__init__(params, sr, use_measures, block_skipping="off")
        self.edges = edges
        self.side = side
        self.axes = axes
        self.frontier_dtype = frontier_dtype

    def spawn(self) -> "_DistributedInterp":
        return _DistributedInterp(
            self.params, BOOL_OR_AND, edges=self.edges, side=self.side,
            axes=self.axes, frontier_dtype=self.frontier_dtype,
        )

    # column routing: shard_map arguments instead of lower-time closures
    def col(self, c):
        kind = c.key[0]
        if kind == "edge":
            _, table, key, attr = c.key
            return self.edges[f"{table}::{key}"][f"m::{attr}"]
        _, entity, attr = c.key
        return self.side[f"attr::{entity}::{attr}"]

    attr_col = col

    def edge_arrays(self, op: HopOp):
        e = self.edges[f"{op.table}::{op.src_key}"]
        return e["src"], e["dst"], e["m::__valid__"]

    def degrees(self, op: DegreeFilterOp):
        return self.side[f"deg::{op.table}::{op.src_key}"]

    def spmv(self, w, src, dst, m, valid, op: HopOp, label: str = ""):
        sr = self.sr
        ew = sr.mask(sr.extend(jnp.take(w, src), m), valid)
        part = sr.segment(ew, dst, op.dom_dst)
        # frontier_dtype=bf16 halves every per-hop all-reduce
        return sr.preduce(part.astype(self.frontier_dtype), self.axes).astype(
            jnp.float32
        )


def compile_frontier_distributed(
    db: DeviceDB, plan: ChainPlan | PhysicalPlan, mesh: Mesh,
    axes: tuple[str, ...] = ("data",),
    batched: bool = False, frontier_dtype=jnp.float32,
    sharded_db: DeviceDB | None = None,
    prefix: int | None = None,
) -> Callable[..., jnp.ndarray]:
    """shard_map execution: frontier vectors replicated, edges sharded; each hop
    computes a local partial accumulator and ⊕-reduces it — the paper's parallel
    design (§6 "Parallel Computing") with the collective replacing spinlocks.

    Edge arrays flow through shard_map *arguments* (in_specs=P(axes)) so each
    device sees only its shard; small arrays (indptr, degrees, entity attrs,
    frontier vectors) are closure constants, i.e. replicated.

    ``sharded_db`` lets callers compiling several entries against one mesh
    (e.g. the engine's single + batched pair) share one ``shard_edges``
    placement instead of device-putting every edge array per compile.

    ``prefix=k`` compiles only the plan's first k ops and returns the raw
    interpreter state (no finalize; AVG runs its weighted pass only) — the
    profiling entry behind ``PreparedQuery.profile()``'s prefix-delta per-op
    timings. Every intermediate state is replicated (each hop ends in its
    ⊕-collective), so the ``P()`` out-spec holds for any prefix.
    """
    phys = ensure_lowered(db, plan)
    names = list(phys.param_names)
    sdb = sharded_db if sharded_db is not None else shard_edges(db, mesh, axes)

    edge_tree = {
        f"{t}::{k}": {
            "src": di.src_ids,
            "dst": di.dst_ids,
            **{f"m::{m}": v for m, v in di.measures.items()},
        }
        for (t, k), di in sdb.indexes.items()
    }
    edge_specs = jax.tree.map(lambda _: P(axes), edge_tree)
    # replicated side tables: entity attributes + per-index degrees — arguments
    # (not closures) so the dry-run can substitute full-scale ShapeDtypeStructs
    side_tree = {
        **{f"attr::{e}::{a}": v for (e, a), v in sdb.entity_attrs.items()},
        **{f"deg::{t}::{k}": di.degrees for (t, k), di in sdb.indexes.items()},
    }
    side_specs = jax.tree.map(lambda _: P(), side_tree)

    def run(edges, side, *args):
        def eval_once(*scalar_args):
            params = dict(zip(names, scalar_args))
            mk = lambda sr, um: _DistributedInterp(
                params, sr, um, edges=edges, side=side, axes=axes,
                frontier_dtype=frontier_dtype,
            )
            if prefix is not None:
                return walk_ir(phys, mk(semiring_for(phys.agg), True), stop=prefix)
            return execute_ir(phys, mk)

        if batched:
            # batched OLAP serving: vmap over parameter vectors inside the
            # shard_map body — frontier becomes [B, dom], hops become SpMM
            return jax.vmap(eval_once)(*args)
        return eval_once(*args)

    smapped = jax.shard_map(
        run, mesh=mesh,
        in_specs=(edge_specs, side_specs) + tuple(P() for _ in names),
        out_specs=P(), check_vma=False,
    )
    jitted = jax.jit(smapped)

    def call(*args):
        return jitted(edge_tree, side_tree, *args)

    call.lowerable = (jitted, edge_tree, side_tree, edge_specs, side_specs)  # dry-run hook
    return call


STRATEGIES = {
    "frontier": compile_frontier,
    "fragment_loop": compile_fragment_loop,
}
