"""Deadline + degradation-ladder execution of prepared queries.

:func:`run_with_policy` executes a :class:`repro.core.engine.PreparedQuery`
under a fault-tolerance policy instead of letting exceptions escape:

  * **Deadline** — a per-query wall-clock budget. Installed in a ContextVar
    (:func:`deadline_scope`) so the executor's instrumented IR walk checks it
    *between ops* (``core.executor`` calls :func:`check_deadline`), and
    checked again around ``block_until_ready`` after every attempt. A query
    that overruns raises :class:`repro.robust.errors.DeadlineExceeded`.

  * **Degradation ladder** — on ``ExecutionError`` / ``ResourceError`` /
    deadline pressure, execution falls to the next cheaper-or-safer rung and
    the result is annotated degraded::

        active          the prepared executable as compiled (block-skipping
                        scalar-prefetch kernels where engaged, fused multi-hop
                        regions where the fusion pass formed them)
        unfused         the same plan with fused regions expanded back to
                        per-hop kernel calls (fusion="off") — sheds the
                        pipelined fused kernels, keeps block skipping
        scan            plain full-scan kernels (block_skipping="off") —
                        sheds the scalar-prefetch machinery
        xla             the pure-XLA reference math (use_pallas=False) —
                        sheds Pallas entirely
        fragment_loop   the paper-faithful scalar reference strategy — the
                        terminus, bit-identical to the frontier strategy by
                        the semiring contract (DESIGN.md §3, §Robustness)

    Every rung interprets the *same lowered physical plan*, so results agree
    bit-for-bit whenever a rung completes (skipped blocks contribute the
    ⊕-identity; the XLA fallback is the kernels' own reference math).

  * **Retry** — failures whose ``retryable`` flag is set retry on the same
    rung with capped exponential backoff + deterministic jitter
    (:class:`RetryPolicy`) before demoting.

Every execution attempt passes the ``runner.execute`` fault-injection site,
so chaos tests can fail/delay attempts without touching kernel internals.

Host-path spans (``obs.trace``; ``repro.<name>`` events in a profiler
trace, nothing at all when tracing is off): ``batch`` (stats ``query``,
``rows``, ``bucket``) around :func:`run_batch_with_policy`, holding
``args`` (validation and padding), ``admit`` (only with admission
control), and per attempt ``attempt`` (``rung``, ``attempt``) holding
``dispatch`` (the executable's call, until it returns), ``device_wait``
(``block_until_ready``) and ``fetch`` (the result to host memory), then
``hops`` — the batched executable's hop work counters, fetched only while
tracing — and ``backoff`` (a retry's sleep). Building a rung's executable
runs in a ``compile`` span.

Outcomes are returned, never raised: :class:`QueryOutcome` carries the value,
the rung that produced it, degradation status, and the terminal
:class:`QueryError` when all rungs failed.
"""
from __future__ import annotations

import random
import time
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from ..obs import trace as T
from ..obs.metrics import REGISTRY, MetricsRegistry
from . import faults
from .admission import AdmissionController
from .errors import DeadlineExceeded, QueryError, wrap_execution_error

#: Rungs in demotion order. ``run_with_policy`` starts at the first rung and
#: walks right on failure; see module docstring for what each sheds.
LADDER = ("active", "unfused", "scan", "xla", "fragment_loop")


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------

_DEADLINE: ContextVar["Deadline | None"] = ContextVar(
    "repro_query_deadline", default=None
)


class Deadline:
    """Wall-clock budget anchored at construction time."""

    __slots__ = ("deadline_ms", "t0")

    def __init__(self, deadline_ms: float):
        self.deadline_ms = float(deadline_ms)
        self.t0 = time.perf_counter()

    def elapsed_ms(self) -> float:
        return (time.perf_counter() - self.t0) * 1e3

    def remaining_ms(self) -> float:
        return self.deadline_ms - self.elapsed_ms()

    def expired(self) -> bool:
        return self.remaining_ms() <= 0.0

    def check(self, where: str = "op") -> None:
        el = self.elapsed_ms()
        if el > self.deadline_ms:
            raise DeadlineExceeded(
                f"deadline of {self.deadline_ms:.0f}ms exceeded at {where}",
                deadline_ms=self.deadline_ms, elapsed_ms=round(el, 3),
                where=where,
            )


class deadline_scope:
    """``with deadline_scope(dl): ...`` — install ``dl`` (or nothing when
    None) as the ambient deadline for the block. The executor's instrumented
    walk consults it between IR ops via :func:`check_deadline`."""

    def __init__(self, deadline: Deadline | None):
        self.deadline = deadline
        self._token = None

    def __enter__(self) -> "Deadline | None":
        self._token = _DEADLINE.set(self.deadline)
        return self.deadline

    def __exit__(self, exc_type, exc, tb) -> bool:
        _DEADLINE.reset(self._token)
        return False


def current_deadline() -> Deadline | None:
    return _DEADLINE.get()


def check_deadline(where: str = "op") -> None:
    """One ContextVar read when no deadline is active (the production fast
    path); raises :class:`DeadlineExceeded` past the budget otherwise."""
    dl = _DEADLINE.get()
    if dl is not None:
        dl.check(where)


# ---------------------------------------------------------------------------
# Policy
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RetryPolicy:
    """Capped exponential backoff with deterministic jitter: attempt k sleeps
    ``min(cap_ms, base_ms · 2^(k−1)) · (1 + jitter·u)``, u ∈ [−1, 1] drawn
    from a ``seed``-determined stream (reproducible chaos runs)."""

    max_attempts: int = 3
    base_ms: float = 5.0
    cap_ms: float = 200.0
    jitter: float = 0.2
    seed: int = 0

    def backoff_ms(self, attempt: int, rng: random.Random) -> float:
        raw = min(self.cap_ms, self.base_ms * (2.0 ** max(attempt - 1, 0)))
        return raw * (1.0 + self.jitter * rng.uniform(-1.0, 1.0))


@dataclass
class RobustPolicy:
    """Everything :func:`run_with_policy` needs: retry knobs, the ladder (a
    prefix/suffix slice of :data:`LADDER` for tests), optional admission
    control, a default deadline, and the metrics registry demotion/error
    counters land on."""

    retry: RetryPolicy = field(default_factory=RetryPolicy)
    ladder: tuple[str, ...] = LADDER
    admission: AdmissionController | None = None
    deadline_ms: float | None = None
    registry: MetricsRegistry = field(default_factory=lambda: REGISTRY)

    def __post_init__(self):
        unknown = [r for r in self.ladder if r not in LADDER]
        if unknown:
            raise ValueError(f"unknown ladder rungs {unknown}; valid: {LADDER}")
        self._rng = random.Random(self.retry.seed)


@dataclass
class QueryOutcome:
    """The structured result of one policy-governed execution. ``status`` is
    ``ok`` (first rung, first attempt), ``degraded`` (answered, but after a
    retry/demotion — ``rung``/``demotions`` say how far it fell), or
    ``error`` (``error`` holds the terminal :class:`QueryError`)."""

    status: str
    value: np.ndarray | None
    rung: str
    attempts: int = 1
    demotions: tuple[str, ...] = ()
    error: QueryError | None = None
    elapsed_ms: float = 0.0

    @property
    def ok(self) -> bool:
        return self.status != "error"

    @property
    def degraded(self) -> bool:
        return self.status == "degraded"

    def to_dict(self) -> dict[str, Any]:
        d: dict[str, Any] = {
            "status": self.status, "rung": self.rung,
            "attempts": self.attempts, "elapsed_ms": round(self.elapsed_ms, 3),
        }
        if self.demotions:
            d["demotions"] = list(self.demotions)
        if self.error is not None:
            d.update(self.error.to_dict())
        return d


# ---------------------------------------------------------------------------
# Rung executables
# ---------------------------------------------------------------------------


def rung_fn(prepared, rung: str, batched: bool = False):
    """The executable for one ladder rung, compiled lazily from the prepared
    query's own device DB + lowered plan and cached on the PreparedQuery, so
    repeated degraded requests pay one compile per (rung, batched) pair."""
    cache = prepared.__dict__.setdefault("_rung_fns", {})
    key = (rung, batched)
    if key in cache:
        return cache[key]
    import jax

    from ..core import executor as X

    from ..core.fuse import unfuse_plan

    if rung == "active":
        fn = prepared.batched_fn if batched else prepared.fn
        if batched and fn is None:  # strategies without a batched entry
            fn = jax.vmap(prepared.fn)
        cache[key] = fn
        return fn
    if rung not in LADDER:
        raise ValueError(f"unknown ladder rung {rung!r}; valid: {LADDER}")
    db, phys = prepared.device_db, prepared.phys
    mk = X.compile_frontier_batched if batched else X.compile_frontier
    name = f"gqfast_frontier{'_batched' if batched else ''}_{rung}"
    with T.span("compile", rung=rung, batched=batched):
        # every rung below "active" runs the unfused twin of the plan: a fault
        # in the fused kernel dispatch must not follow the query down the
        # ladder (the frontier interps replay fused regions per-op only when
        # told to)
        uphys = unfuse_plan(phys) if phys is not None else phys
        if rung == "unfused":
            fn = mk(db, uphys, block_skipping=prepared.block_skipping,
                    fusion="off", name=name)
        elif rung == "scan":
            fn = mk(db, uphys, block_skipping="off", fusion="off", name=name)
        elif rung == "xla":
            fn = mk(db, uphys, block_skipping="off", use_pallas=False,
                    fusion="off", name=name)
        else:  # fragment_loop
            single = X.compile_fragment_loop(db, uphys, use_pallas=False)
            fn = jax.vmap(single) if batched else single
    cache[key] = fn
    return fn


# ---------------------------------------------------------------------------
# The policy-governed execution loop
# ---------------------------------------------------------------------------


def hop_stats(hops: list[dict]) -> dict:
    """The ``hops`` span's stats from ``HopCounts.decode()``: totals
    ``rows``, ``gathers`` and ``gather_rows`` (the rows of the hops whose
    gathers are counted), and per hop ``h<k>`` = ``"<label> rows=<n>
    gathers=<n|-> active=<n|-> chunks=<n>"``."""
    st = {"rows": 0, "gathers": 0, "gather_rows": 0}
    for h in hops:
        st["rows"] += h["rows"]
        if h["gathers"] is not None:
            st["gathers"] += h["gathers"]
            st["gather_rows"] += h["rows"]
        key, _, label = h["label"].partition(":")
        gathers, active = (("-" if v is None else v)
                           for v in (h["gathers"], h["active_chunks"]))
        st[key] = (f"{label} rows={h['rows']} gathers={gathers} "
                   f"active={active} chunks={h['chunks']}")
    return st


def _attempt(prepared, rung: str, args, deadline: Deadline | None,
             batched: bool):
    """One execution attempt on one rung: fault site → compile/lookup →
    call → device fence → deadline check → fetch. Raises QueryError on any
    failure. While tracing, a counting executable's hop counters are
    fetched too and emitted as the ``hops`` span's stats."""
    import jax

    faults.fire("runner.execute", rung=rung, query=prepared.sql.strip()[:80])
    counts = None
    try:
        with deadline_scope(deadline):
            fn = rung_fn(prepared, rung, batched=batched)
            counted = getattr(fn, "counted", None) if T.tracing() else None
            with T.span("dispatch"):
                if counted is not None:
                    out, counts = counted(*args)
                else:
                    out = fn(*args)
            with T.span("device_wait"):
                jax.block_until_ready(out)
    except QueryError:
        raise
    except Exception as e:  # noqa: BLE001 — normalize foreign exceptions
        raise wrap_execution_error(e, rung=rung, strategy=prepared.strategy)
    if deadline is not None:
        deadline.check("block_until_ready")
    with T.span("fetch"):
        value = np.asarray(out)
    if counts is not None:
        with T.span("hops") as sp:
            sp.annotate(**hop_stats(counts.decode()))
    return value


def _run_ladder(prepared, args, policy: RobustPolicy,
                deadline: Deadline | None, batched: bool,
                t0: float) -> QueryOutcome:
    reg = policy.registry
    attempts, demotions = 0, []
    last_err: QueryError | None = None
    for rung in policy.ladder:
        retries = 0
        while True:
            attempts += 1
            try:
                with T.span("attempt", rung=rung, attempt=attempts):
                    value = _attempt(prepared, rung, args, deadline, batched)
                status = (
                    "ok" if attempts == 1 and not demotions else "degraded"
                )
                if status == "degraded":
                    reg.counter("robust.degraded_results").inc()
                return QueryOutcome(
                    status, value, rung, attempts, tuple(demotions),
                    elapsed_ms=(time.perf_counter() - t0) * 1e3,
                )
            except QueryError as e:
                last_err = e.with_context(rung=rung)
                reg.counter(f"robust.errors.{e.code}").inc()
                if isinstance(e, DeadlineExceeded):
                    reg.counter("robust.deadline_exceeded").inc()
                # a spent deadline is terminal: no rung can answer in time
                if deadline is not None and deadline.expired():
                    return QueryOutcome(
                        "error", None, rung, attempts, tuple(demotions),
                        error=last_err,
                        elapsed_ms=(time.perf_counter() - t0) * 1e3,
                    )
                if e.retryable and retries < policy.retry.max_attempts - 1:
                    retries += 1
                    reg.counter("robust.retries").inc()
                    wait = policy.retry.backoff_ms(retries, policy._rng)
                    if deadline is None or deadline.remaining_ms() > wait:
                        with T.span("backoff", ms=round(wait, 3)):
                            time.sleep(wait / 1e3)
                        continue
                break  # exhausted retries (or no time to back off): demote
        demotions.append(rung)
        reg.counter("robust.demotions").inc()
        reg.counter(f"robust.demotions.{rung}").inc()
    return QueryOutcome(
        "error", None, policy.ladder[-1], attempts, tuple(demotions),
        error=last_err, elapsed_ms=(time.perf_counter() - t0) * 1e3,
    )


def run_with_policy(prepared, params: dict, deadline_ms: float | None = None,
                    policy: RobustPolicy | None = None) -> QueryOutcome:
    """Execute one parameter binding of ``prepared`` under ``policy``.
    Returns a :class:`QueryOutcome`; never raises for query-shaped failures
    (validation, admission, execution, deadline) — those come back as
    ``status="error"`` with the typed error attached."""
    policy = policy if policy is not None else RobustPolicy()
    t0 = time.perf_counter()
    dms = deadline_ms if deadline_ms is not None else policy.deadline_ms
    deadline = Deadline(dms) if dms is not None else None
    try:
        prepared.validate_params(params)
        if policy.admission is not None:
            policy.admission.admit(prepared, batch=1)
    except QueryError as e:
        policy.registry.counter(f"robust.errors.{e.code}").inc()
        return QueryOutcome(
            "error", None, policy.ladder[0], 0, error=e,
            elapsed_ms=(time.perf_counter() - t0) * 1e3,
        )
    args = [params[n] for n in prepared.param_names]
    return _run_ladder(prepared, args, policy, deadline, False, t0)


def run_batch_with_policy(
    prepared, param_arrays: dict, deadline_ms: float | None = None,
    policy: RobustPolicy | None = None,
) -> list[QueryOutcome]:
    """Policy-governed form of ``PreparedQuery.execute_batch``: B parameter
    bindings in one pass, one :class:`QueryOutcome` per binding (all rows of
    a surviving batch share status/rung; a rejected/failed batch yields per-
    row error outcomes). Admission may *demote* an over-budget batch to
    serial single-query execution — degraded, but within budget."""
    with T.span("batch", query=getattr(prepared, "query_id", "")) as sp:
        return _run_batch(prepared, param_arrays, deadline_ms, policy, sp)


def _run_batch(prepared, param_arrays: dict, deadline_ms: float | None,
               policy: RobustPolicy | None, sp) -> list[QueryOutcome]:
    from ..core.engine import batch_bucket

    policy = policy if policy is not None else RobustPolicy()
    t0 = time.perf_counter()
    dms = deadline_ms if deadline_ms is not None else policy.deadline_ms
    deadline = Deadline(dms) if dms is not None else None
    try:
        with T.span("args"):
            args, B = prepared._batch_args(param_arrays)
            # pad to the bucket (repeating the last row) so recompiles stay
            # bounded; serial demotion reads only the first B rows
            bucket = batch_bucket(B)
            padded = args
            if bucket != B:
                padded = [
                    np.concatenate([a, np.repeat(a[-1:], bucket - B, axis=0)])
                    for a in args
                ]
    except QueryError as e:
        policy.registry.counter(f"robust.errors.{e.code}").inc()
        n = _best_effort_batch_len(param_arrays)
        out = QueryOutcome("error", None, policy.ladder[0], 0, error=e)
        return [out] * max(n, 1)
    sp.annotate(rows=B, bucket=bucket)
    serial = False
    if policy.admission is not None:
        try:
            with T.span("admit"):
                decision = policy.admission.admit(prepared, batch=B,
                                                  allow_demote=True)
            serial = decision.action == "demote"
        except QueryError as e:
            policy.registry.counter(f"robust.errors.{e.code}").inc()
            out = QueryOutcome("error", None, policy.ladder[0], 0, error=e)
            return [out] * B
    if serial:
        policy.registry.counter("robust.degraded_results").inc(B)
        outs = []
        for b in range(B):
            params = {
                n: np.asarray(a[b]).item()
                for n, a in zip(prepared.param_names, args)
            }
            oc = run_with_policy(prepared, params, deadline_ms=dms,
                                 policy=policy)
            if oc.status == "ok":  # serial demotion is itself a degradation
                oc.status = "degraded"
            outs.append(oc)
        return outs
    oc = _run_ladder(prepared, padded, policy, deadline, True, t0)
    if oc.value is not None:
        rows = oc.value[:B]
        return [
            QueryOutcome(oc.status, rows[b], oc.rung, oc.attempts,
                         oc.demotions, elapsed_ms=oc.elapsed_ms)
            for b in range(B)
        ]
    return [oc] * B


def _best_effort_batch_len(param_arrays: dict) -> int:
    for v in param_arrays.values():
        try:
            return len(v)
        except TypeError:
            continue
    return 1
