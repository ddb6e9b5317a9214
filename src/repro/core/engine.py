"""GQ-Fast engine facade (paper Fig. 4 architecture).

``GQFastDatabase`` = Loader: builds both fragment indices per relationship table
(+ metadata: encodings, space). ``GQFastEngine`` = Query Processor: SQL → RQNA
(parse + normalize/verify) → physical chain plan → compiled executable
(prepare once / execute many, as JDBC-style prepared statements)."""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from ..obs import trace as T
from ..robust import faults as _faults
from ..robust.admission import PreparedCache
from ..robust.errors import QueryError, ValidationError
from . import executor as X
from .algebra import ChainPlan
from .fragments import FragmentIndex, build_index
from .fuse import fuse_plan, fusion_groups, has_fused
from .lower import PhysicalPlan, lower
from .planner import plan_query
from .schema import RelationshipTable, Schema
from .sql import parse


class GQFastDatabase:
    """In-memory GQ-Fast database: both directions of every relationship table.

    ``keep_packed`` (default True, matching ``fragments.build_index``) keeps
    the host-side bit-packed words on each ``ColumnFragments`` — the kernel
    wire layout the device column store reuses. Setting it False only trades
    host memory for a re-pack when a packed device encoding is chosen; the
    device representation is governed solely by ``device_encodings``
    (``"auto"`` | ``"dense"`` | ``"packed"`` | per-column dict keyed by
    ``(table, key, column)`` — see ``executor.build_device_db``). Deployments
    that only run the fallback strategies (``fragment_loop`` / a mesh) should
    pass ``device_encodings="dense"``: their prepares materialize every packed
    column anyway, so packed storage would cost packed *plus* dense bytes
    (visible as ``space_report()["device"]["materialized_bytes"]``)."""

    def __init__(
        self,
        schema: Schema,
        encodings: dict[tuple[str, str, str], str] | None = None,
        account_space: bool = True,
        keep_packed: bool = True,
        device_encodings: str | dict | None = "auto",
    ):
        schema.validate()
        self.schema = schema
        self.host_indexes: dict[tuple[str, str], FragmentIndex] = {}
        for rel in schema.relationships.values():
            for key in (rel.fk1, rel.fk2):
                enc = {
                    col: e
                    for (t, k, col), e in (encodings or {}).items()
                    if t == rel.name and k == key
                }
                self.host_indexes[(rel.name, key)] = build_index(
                    schema, rel, key, enc or None,
                    keep_packed=keep_packed, account_space=account_space,
                )
        self.device = X.build_device_db(schema, self.host_indexes, device_encodings)

    @classmethod
    def from_parts(cls, schema: Schema, host_indexes, device) -> "GQFastDatabase":
        """Assemble a database from already-built parts without re-running
        index construction or device encoding — the snapshot restore path
        (``storage/snapshot.py``), which rebuilds host indexes and device
        columns directly from verified stored bytes."""
        schema.validate()
        db = cls.__new__(cls)
        db.schema = schema
        db.host_indexes = host_indexes
        db.device = device
        return db

    def space_report(self) -> dict[str, Any]:
        """Host byte-array accounting (paper §5 analytic model) plus the
        ``device`` section: real bytes the device column store holds, per
        column, with the decoded-CSR baseline for the compression ratio."""
        from ..storage import device_space_report

        rep: dict[str, Any] = {"indexes": {}, "total_bytes": 0}
        for (t, k), idx in self.host_indexes.items():
            cols = {
                c: {"encoding": cf.encoding, "bytes": cf.encoded_bytes}
                for c, cf in idx.columns.items()
            }
            b = idx.total_bytes()
            rep["indexes"][f"I_{t}.{k}"] = {"columns": cols, "lookup_bytes": idx.lookup_bytes(), "bytes": b}
            rep["total_bytes"] += b
        rep["device"] = device_space_report(self.device)
        return rep


#: Ragged batches pad up to one of these sizes so the batched executable
#: compiles a bounded number of times: powers of two up to 64, then
#: multiples of 64 (a B=65 burst compiles the 128 bucket, not its own).
BATCH_BUCKET_CAP = 64


def batch_bucket(b: int) -> int:
    """Smallest bucket ≥ b: next power of two up to BATCH_BUCKET_CAP, then
    the next multiple of BATCH_BUCKET_CAP."""
    if b <= BATCH_BUCKET_CAP:
        return 1 << (b - 1).bit_length()
    return -(-b // BATCH_BUCKET_CAP) * BATCH_BUCKET_CAP


def query_id(sql: str) -> str:
    """Eight hex digits naming a query text (whitespace-insensitive)."""
    text = " ".join(sql.split()).encode()
    return hashlib.blake2s(text, digest_size=4).hexdigest()


@dataclass
class PreparedQuery:
    sql: str
    plan: ChainPlan
    fn: Callable[..., Any]
    param_names: list[str]
    group_entity: str | None
    phys: PhysicalPlan | None = None  # lowered IR (None only for legacy callers)
    batched_fn: Callable[..., Any] | None = None  # SpMM batch entry (frontier)
    strategy: str = "frontier"  # resolved (auto → the picked one)
    block_skipping: str = "auto"  # frontier-sparsity mode baked into fn
    fusion: str = "auto"  # multi-hop fusion mode baked into fn
    hop_estimates: list[dict] | None = None  # per-hop selectivity estimates
    plan_sig: str | None = None  # unfused op-signature (calibration key)
    calibration: Any = None  # engine's CalibrationStore (shared, may be None)
    # observability handles (DESIGN.md §Observability): the device DB for
    # memory reports and the mesh/sharded-DB triple the distributed profiler
    # needs to rebuild prefix executables against the same placement
    device_db: Any = None
    mesh: Any = None
    shard_axes: tuple = ("data",)
    sharded_db: Any = None
    # short stable id of the query text: the ``query`` stat of the runner's
    # ``batch`` span, so a trace names the shape behind each batch
    query_id: str = field(init=False, default="")

    def __post_init__(self):
        self.query_id = query_id(self.sql)

    def validate_params(self, params: dict) -> None:
        """Typed parameter-binding validation: every declared parameter bound,
        no unknown names — callers get a :class:`ValidationError` instead of a
        raw KeyError out of the argument zip."""
        missing = [n for n in self.param_names if n not in params]
        if missing:
            raise ValidationError(
                f"missing parameters: {missing}",
                missing=missing, expected=list(self.param_names),
                query=" ".join(self.sql.split()),
            )
        extra = [n for n in params if n not in self.param_names]
        if extra:
            raise ValidationError(
                f"unknown parameters: {extra}",
                unknown=extra, expected=list(self.param_names),
                query=" ".join(self.sql.split()),
            )

    def __call__(self, **params) -> np.ndarray:
        self.validate_params(params)
        args = [params[n] for n in self.param_names]
        if T.current() is None:  # the zero-overhead default path
            return np.asarray(self.fn(*args))
        with T.span("execute", strategy=self.strategy,
                    query=" ".join(self.sql.split())) as sp:
            out = sp.fence(self.fn(*args))  # kernel_ms: device-done
            return np.asarray(out)

    def profile(self, reps: int = 3, **params) -> Any:
        """Execute once under instrumentation and return a
        :class:`repro.obs.profile.QueryProfile`: per-IR-op wall/kernel times,
        predicted-vs-observed per-hop active fractions (mispredictions beyond
        2× increment the ``strategy_mispredict`` counter), device-memory
        report, and the fenced end-to-end median of ``reps`` runs. The profile
        ``result`` comes from the same compiled executable ``__call__`` runs,
        so it is bit-identical to plain execution."""
        from ..obs.profile import profile_prepared

        return profile_prepared(self, params, reps=reps)

    def explain(self, analyze: bool = False, **params) -> str:
        """Human-readable execution summary: the op pipeline, the resolved
        strategy, the block-skipping mode, and per-hop estimated active
        fractions (the selectivity model behind strategy choice and the
        skip-vs-scan heuristic, DESIGN.md §Sparsity).

        ``analyze=True`` additionally executes the query once with the given
        parameter bindings and appends the :meth:`profile` report: per-IR-op
        wall/kernel time, predicted-vs-observed hop fractions (mispredicts
        flagged), and the device-memory footprint — EXPLAIN ANALYZE."""
        lines = [
            f"query: {' '.join(self.sql.split())}",
            f"strategy: {self.strategy}",
            f"block_skipping: {self.block_skipping}",
            f"fusion: {self.fusion}",
            f"params: {self.param_names}",
        ]
        if self.phys is not None:
            sig = " -> ".join(type(op).__name__ for op in self.phys.ops)
            lines.append(f"ops: {sig}")
            for g in fusion_groups(self.phys):
                lines.append(f"  fused region: {g}")
        for label, route in self.hop_routes(batched=self.batched_fn is not None):
            lines.append(f"  hop {label}: {route}")
        for h in self.hop_estimates or []:
            lines.append(
                f"  hop I_{h['table']}.{h['src_key']}: "
                f"est_active_fraction={h['est_active_fraction']:.4g}"
            )
        if analyze:
            lines.append(self.profile(**params).render())
        return "\n".join(lines)

    def hop_routes(self, batched: bool = False) -> list[tuple[str, str]]:
        """Where each hop of the plan (mask sub-programs included) runs:
        ``pallas`` — the hop kernel over the pull stream of the reverse
        index (``executor.PullStream``) — ``pallas (source-sorted)`` — the
        same kernel over the hop's own index, where the database holds no
        reverse index — or ``xla: <reason>`` for hops that stay on XLA by
        design. The ladder can still demote a Pallas hop at run time; this
        is the plan's route."""
        from .lower import HopOp, SeedOp, batch_dependent, iter_flat_ops

        def walk(phys):
            for op in iter_flat_ops(phys):
                if isinstance(op, SeedOp):
                    for prog in op.programs:
                        yield from walk(prog)
                if not isinstance(op, HopOp):
                    continue
                label = f"{op.table}.{op.src_key}->{op.dst_entity}"
                if self.strategy != "frontier":
                    route = f"xla: {self.strategy} strategy"
                elif batched and op.measure is not None and batch_dependent(op.measure):
                    route = "xla: per-row measure (no shared edge stream)"
                elif op.pull is None:
                    route = "pallas (source-sorted)"
                else:
                    route = "pallas"
                yield label, route

        return list(walk(self.phys)) if self.phys is not None else []

    def _batch_args(self, param_arrays: dict) -> tuple[list[np.ndarray], int]:
        """Validate one [B] array (or Python list) per parameter: every
        parameter present, none scalar, all the same length."""
        if not self.param_names:
            raise ValidationError(
                "execute_batch needs a parameterized query (this one has none);"
                " call the prepared query directly instead"
            )
        missing = [n for n in self.param_names if n not in param_arrays]
        if missing:
            raise ValidationError(
                f"execute_batch missing parameter arrays: {missing}",
                missing=missing, expected=list(self.param_names),
            )
        args, B = [], None
        for n in self.param_names:
            a = np.asarray(param_arrays[n])
            if a.ndim == 0:
                raise ValidationError(
                    f"execute_batch parameter {n!r} is a scalar; pass a list or"
                    " 1-D array with one value per query (a scalar would"
                    " silently broadcast to every query in the batch)",
                    param=n,
                )
            if a.ndim != 1:
                raise ValidationError(
                    f"execute_batch parameter {n!r} must be 1-D, got shape {a.shape}",
                    param=n, shape=a.shape,
                )
            if B is None:
                B = a.shape[0]
            elif a.shape[0] != B:
                raise ValidationError(
                    f"ragged batch: parameter {n!r} has length {a.shape[0]} but"
                    f" {self.param_names[0]!r} has length {B}; all parameter"
                    " arrays must have one entry per query",
                    param=n,
                )
            args.append(a)
        if B == 0:
            raise ValidationError("execute_batch got empty parameter arrays")
        return args, B

    def execute_batch(self, **param_arrays) -> np.ndarray:
        """Serve B parameter bindings of this query in one pass → [B, out_dom].

        On the frontier strategy this runs the batched SpMM executable
        (``compile_frontier_batched``): each hop streams the edge arrays once
        for the whole batch. Ragged B pads up to a bucket size (repeating the
        last row; the pad rows are sliced off) so recompiles are bounded.
        Strategies without a batched interpreter (fragment_loop, distributed
        meshes) fall back to ``jax.vmap`` over the single-query executable —
        same results, no edge-stream reuse."""
        args, B = self._batch_args(param_arrays)
        bucket = batch_bucket(B)
        if bucket != B:  # bound recompiles on the fallback path too
            args = [
                np.concatenate([a, np.repeat(a[-1:], bucket - B, axis=0)])
                for a in args
            ]
        if self.batched_fn is None:
            import jax

            return np.asarray(jax.vmap(self.fn)(*args))[:B]
        return np.asarray(self.batched_fn(*args))[:B]


class CalibrationStore:
    """Observed per-hop active fractions keyed by (unfused) plan signature.

    ``profile_prepared`` records what a real execution actually touched; the
    next ``prepare`` of any query lowering to the same op shape consults the
    observation in :meth:`GQFastEngine._pick_strategy` instead of trusting the
    lower-time fanout model alone — profiling a workload once recalibrates
    strategy choice for its whole plan family. Bounded (LRU-ish: dict
    insertion order, oldest evicted) so long-lived engines cannot grow it
    without limit."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._obs: dict[str, list[float]] = {}

    def record(self, plan_sig: str, fractions: list) -> None:
        vals = [float(f) for f in fractions if f is not None]
        if not vals:
            return
        self._obs.pop(plan_sig, None)
        self._obs[plan_sig] = vals
        while len(self._obs) > self.max_entries:
            self._obs.pop(next(iter(self._obs)))

    def get(self, plan_sig: str) -> list[float] | None:
        return self._obs.get(plan_sig)

    def __len__(self) -> int:
        return len(self._obs)


class GQFastEngine:
    def __init__(self, db: GQFastDatabase, strategy: str = "frontier",
                 mesh=None, shard_axes: tuple[str, ...] = ("data",),
                 max_prepared: int = 64):
        self.db = db
        self.strategy = strategy
        self.mesh = mesh
        self.shard_axes = shard_axes
        # fixed-size LRU: each entry pins a traced executable pair, so the
        # prepare cache must not grow without bound under many query shapes
        self._cache: PreparedCache = PreparedCache(max_prepared)
        # per-plan-signature observed active fractions (fed by profile runs)
        self.calibration = CalibrationStore()
        # the mesh's edge-sharded placement, shared by every prepared query
        self._sharded_db = None
        if mesh is None:  # the single-chip hop kernels read pull streams
            X.attach_pull_streams(db.device)

    def invalidate_prepared(self) -> int:
        """Drop every cached prepared query. Required after the device arrays
        under the executables change in place — a scrubber heal or a snapshot
        generation swap — because traced executables close over the old
        buffers. Returns the number of entries dropped."""
        self._sharded_db = None
        return self._cache.clear()

    def prepare(self, sql: str, block_skipping: str = "auto",
                fusion: str = "auto") -> PreparedQuery:
        """Compile ``sql`` once for repeated execution. ``block_skipping``
        ('auto' | 'on' | 'off') sets the frontier-sparsity mode baked into the
        executable (DESIGN.md §Sparsity): 'auto' skips inactive edge blocks
        when the estimated/observed active fraction is small, 'on' forces the
        scalar-prefetch kernels, 'off' always full-scans. ``fusion`` ('auto' |
        'on' | 'off') controls multi-hop region fusion (DESIGN.md §Pipelined
        fusion): adjacent HopOp chains execute as one kernel pass with the
        intermediate frontier resident in VMEM scratch; 'auto' additionally
        falls back per-region when the intermediate would overflow the VMEM
        budget. Frontier strategy only — fragment_loop and meshes always run
        the unfused plan."""
        from ..kernels.ops import BLOCK_SKIPPING_MODES, FUSION_MODES

        if block_skipping not in BLOCK_SKIPPING_MODES:
            raise ValidationError(
                f"block_skipping must be one of {BLOCK_SKIPPING_MODES}, "
                f"got {block_skipping!r}",
                block_skipping=block_skipping, valid=BLOCK_SKIPPING_MODES,
            )
        if fusion not in FUSION_MODES:
            raise ValidationError(
                f"fusion must be one of {FUSION_MODES}, got {fusion!r}",
                fusion=fusion, valid=FUSION_MODES,
            )
        key = (sql, self.strategy, block_skipping, fusion)
        cached = self._cache.get(key)
        if cached is not None:
            return cached
        _faults.fire("engine.prepare", query=" ".join(sql.split()))
        with T.span("prepare", query=" ".join(sql.split())):
            try:
                with T.span("parse"):
                    ast = parse(sql)
                with T.span("plan"):
                    plan = plan_query(self.db.schema, ast)
                # lower once: every strategy interprets the same physical IR,
                # and the per-execute mask/ref-resolution work is hoisted out
                # of the hot path
                with T.span("lower"):
                    phys = lower(self.db.device, plan)
            except QueryError as e:
                # every prepare-stage failure carries the query text
                raise e.with_context(query=" ".join(sql.split()))
            # the UNFUSED signature keys the calibration store, so a fused
            # and an unfused prepare of the same shape share observations
            plan_sig = " -> ".join(phys.op_signature())
            names = list(phys.param_names)
            bfn, sdb = None, None
            # the compile span covers executable construction; jax traces and
            # XLA-compiles lazily, so the first execute span absorbs that cost
            with T.span("compile") as csp:
                if self.mesh is not None:
                    strategy = "distributed"  # skipping n/a: sharded XLA hops
                    if self._sharded_db is None:
                        self._sharded_db = X.shard_edges(
                            self.db.device, self.mesh, self.shard_axes
                        )
                    sdb = self._sharded_db
                    fn = X.compile_frontier_distributed(
                        self.db.device, phys, self.mesh, self.shard_axes,
                        sharded_db=sdb,
                    )
                    if names:  # shard_map body vmaps over the parameter vectors
                        bfn = X.compile_frontier_distributed(
                            self.db.device, phys, self.mesh, self.shard_axes,
                            batched=True, sharded_db=sdb,
                        )
                else:
                    strategy = self.strategy
                    if strategy == "auto":
                        strategy = self._pick_strategy(plan, plan_sig)
                    if strategy == "frontier" and fusion != "off":
                        with T.span("fuse"):
                            phys = fuse_plan(phys)
                    if strategy == "frontier":
                        fn = X.compile_frontier(
                            self.db.device, phys,
                            block_skipping=block_skipping, fusion=fusion,
                        )
                    else:
                        fn = X.STRATEGIES[strategy](
                            self.db.device, phys, block_skipping=block_skipping
                        )
                    if strategy == "frontier" and names:
                        # the SpMM serving path: one edge stream per hop for
                        # the whole batch. fragment_loop keeps the vmap
                        # fallback so its batched results stay bit-identical
                        # to its own single-query calls.
                        bfn = X.compile_frontier_batched(
                            self.db.device, phys,
                            block_skipping=block_skipping, fusion=fusion,
                        )
                csp.annotate(strategy=strategy, n_ops=len(phys.ops),
                             fused=has_fused(phys))
            pq = PreparedQuery(
                sql, plan, fn, names, plan.group_entity, phys, bfn,
                strategy=strategy, block_skipping=block_skipping,
                fusion=fusion, hop_estimates=self._hop_fractions(plan),
                plan_sig=plan_sig, calibration=self.calibration,
                device_db=self.db.device, mesh=self.mesh,
                shard_axes=self.shard_axes, sharded_db=sdb,
            )
        self._cache.put(key, pq)
        return pq

    def _hop_fractions(self, plan: ChainPlan) -> list[dict]:
        """Per-hop estimated active fraction: seed cardinality pushed through
        p90 fanouts. ``frontier_est × p90(degree)`` edges are expected to be
        touched out of E — the 90th-percentile fragment length rather than
        the mean, because graph degree distributions are heavy-tailed and a
        seed that lands on a hub makes the *average* a serious
        under-prediction of touched work (the mispredict pattern the profile
        counter kept flagging); p90 over-predicts the median seed slightly,
        which only errs toward the throughput-safe frontier strategy. The
        reached-destination count caps at the dst domain, and a mask seed
        starts whole-domain (fraction 1). This is the shared selectivity
        model behind ``_pick_strategy`` and the explain() report; the runtime
        skip heuristic measures the real support instead (kernels/ops.py)."""
        from .algebra import RelHop, SeedIds

        if isinstance(plan.seed, SeedIds):
            ids = plan.seed.ids if isinstance(plan.seed.ids, list) else [plan.seed.ids]
            frontier_est: float | None = float(len(ids))
        else:
            frontier_est = None  # mask seed: whole-domain support
        hops = []
        for s in plan.steps:
            if not isinstance(s, RelHop) or s.degree_filter:
                continue
            idx = self.db.host_indexes[(s.table, s.src_key)]
            E = max(idx.num_edges, 1)
            h = max(idx.indptr.shape[0] - 1, 1)
            degrees = np.diff(np.asarray(idx.indptr))
            fanout = float(np.percentile(degrees, 90)) if degrees.size else 0.0
            fanout = max(fanout, E / h)  # p90 never below the mean edge share
            if frontier_est is None:
                frontier_est = float(h)
            touched = min(frontier_est * fanout, float(E))
            hops.append({
                "table": s.table,
                "src_key": s.src_key,
                "est_active_fraction": touched / E,
            })
            frontier_est = min(touched, float(self.db.schema.domain_size(s.dst_entity)))
        return hops

    def _pick_strategy(self, plan: ChainPlan, plan_sig: str | None = None) -> str:
        """Beyond-paper: cost-based strategy choice. The paper's fragment-at-a-
        time execution is *work-efficient* (touches only reachable fragments);
        the vectorized frontier pass is *throughput-efficient* (whole-relation
        SpMV). The seed-cardinality × fanout selectivity estimate
        (:meth:`_hop_fractions`) decides: if every hop touches a small
        fraction of its relation, the scalar fragment walk wins; once any hop
        goes dense, the vectorized frontier does (EXPERIMENTS.md §Perf).
        When the calibration store holds *observed* fractions for this plan
        signature (a prior profile run of the same op shape), those replace
        the model — measured reality beats the fanout estimate."""
        from .algebra import SeedIds

        if not isinstance(plan.seed, SeedIds):
            return "frontier"  # mask seeds are whole-domain already
        fracs = None
        if plan_sig is not None:
            fracs = self.calibration.get(plan_sig)
        if fracs is None:
            fracs = [h["est_active_fraction"] for h in self._hop_fractions(plan)]
        worst_fraction = max(fracs, default=1.0)
        # crossover measured on this host (benchmarks/perf_baseline): the scalar
        # loop wins while < ~15% of the relation is touched; on TPU the vector
        # path's advantage is larger, so deployments should retune this knob
        return "fragment_loop" if worst_fraction < 0.15 else "frontier"

    def query(self, sql: str, **params) -> np.ndarray:
        return self.prepare(sql)(**params)

    def query_topk(self, sql: str, k: int = 10, **params) -> list[tuple[int, float]]:
        scores = self.query(sql, **params)
        return self._topk(scores, k)

    def query_topk_batch(
        self, sql: str, k: int = 10, **param_arrays
    ) -> list[list[tuple[int, float]]]:
        """Batched form of :meth:`query_topk`: one [B]-array per parameter,
        one SpMM pass, one top-k list per query (dashboard panels)."""
        scores = self.prepare(sql).execute_batch(**param_arrays)
        return [self._topk(row, k) for row in scores]

    @staticmethod
    def _topk(scores: np.ndarray, k: int) -> list[tuple[int, float]]:
        idx = np.argsort(-scores)[:k]
        return [(int(i), float(scores[i])) for i in idx if scores[i] != 0]
