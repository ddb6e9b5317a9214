"""Public entries for the Pallas kernels.

On a CPU backend the kernels execute via ``interpret=True`` (the Pallas body
run as XLA ops — correctness only); on TPU they run compiled by Mosaic.
``use_pallas=False`` selects the pure-XLA path (same math, from
:mod:`repro.kernels.ref`), the degradation ladder's ``xla`` rung.

Every hop entry (single/batched × dense/packed) lands in :func:`hop`, which
runs :func:`repro.kernels.fragment_spmv.fragment_hop`; a single query is a
batch of one. Two skipping mechanisms, both bit-identical to a full scan
(skipped work contributes the ⊕-identity):

  * **frontier chunks** (always on unless ``block_skipping='off'``): the
    kernel gathers only from 128-entry frontier chunks that hold a
    non-identity value — the served path's skipping, since its edges are
    destination-sorted;
  * **edge blocks** (``blocks=(src_min, src_max)`` of source-sorted edges,
    kernels/active.py): the scalar-prefetched block list drives the edge
    streams' ``index_map`` so unreachable blocks are never DMA'd. Two tiers:
    **eager** (concrete frontier — the list is computed in numpy, its
    capacity bucketed to a power of two, and the grid really shrinks; 'auto'
    bails back to the scan above ``SKIP_BLOCK_FRACTION``) and **traced**
    (frontier is a jit tracer — full-capacity list, inactive steps do
    nothing; 'auto' picks at runtime via ``lax.cond``).

Every entry takes a static ``label`` (the executor's hop) that reaches the
kernel's ``kernel_metadata`` and so the device trace; a fused region's label
names both member hops. :func:`fuses` and :func:`streamed_blocks` state the
dispatch decisions the executor's hop work counters need.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import active as _active
from . import ref
from ..obs import trace as _obs_trace
from ..robust import faults as _faults
from ..robust.errors import ValidationError
from .bitmap_ops import bitmap_and as _bitmap_and
from .bitmap_ops import bitmap_and_popcount as _bitmap_and_popcount
from .bitunpack import bitunpack as _bitunpack
from .fragment_spmv import GROUP_ROWS, n_edges, padded
from .fragment_spmv import IDENTITY as _IDENTITY
from .fragment_spmv import fragment_hop as _fragment_hop
from .fragment_spmv_fused import apply_mask as _fused_apply_mask
from .fragment_spmv_fused import binarize as _fused_binarize
from .fragment_spmv_fused import fragment_hops_fused as _fragment_hops_fused
from .params import FUSED_VMEM_BUDGET_BYTES

BLOCK_SKIPPING_MODES = ("off", "on", "auto")

#: Pipelined-region dispatch: 'off' always composes the member hops through
#: the unfused kernels, 'on' forces the fused kernel, 'auto' fuses unless the
#: VMEM-resident intermediate (4·n_mid·B bytes) exceeds FUSED_VMEM_BUDGET_BYTES.
FUSION_MODES = ("off", "on", "auto")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _check_skip_mode(block_skipping: str) -> None:
    if block_skipping not in BLOCK_SKIPPING_MODES:
        raise ValidationError(
            f"unknown block_skipping mode {block_skipping!r}",
            block_skipping=block_skipping, valid=BLOCK_SKIPPING_MODES,
        )


def _may_skip(E: int, blocks, block_skipping: str) -> bool:
    """Whether a block list can apply to the hop at all."""
    _check_skip_mode(block_skipping)
    if block_skipping == "off" or blocks is None or E == 0:
        return False
    # nothing to skip on a 1-block index; 'on' still engages the active
    # kernel so small shapes exercise the real code path
    return _active.n_edge_blocks(E) > 1 or block_skipping == "on"


def _skip_threshold(E: int) -> int:
    return max(1, int(_active.SKIP_BLOCK_FRACTION * _active.n_edge_blocks(E)))


def _plan_skip(w, op: str, E: int, blocks, block_skipping: str):
    """Decide scan vs skip for one hop. ``None`` → full scan; otherwise
    ``(block_idx, n_active, mode)`` with mode 'static' (commit to the active
    kernel now) or 'cond' (traced 'auto': pick at runtime via lax.cond)."""
    if not _may_skip(E, blocks, block_skipping):
        return None
    nb = _active.n_edge_blocks(E)
    src_min, src_max = blocks
    zero = _IDENTITY[op]
    if isinstance(w, jax.core.Tracer):
        bi, na = _active.active_block_list(
            w, zero, jnp.asarray(src_min), jnp.asarray(src_max)
        )
        _obs_trace.annotate(skip_tier="traced", n_blocks=nb)
        return bi, na, ("cond" if block_skipping == "auto" else "static")
    support = np.asarray(w != zero)
    if support.ndim == 2:
        support = support.any(axis=0)
    bi, na, frac = _active.active_block_list_np(support, src_min, src_max)
    if block_skipping == "auto" and frac > _active.SKIP_BLOCK_FRACTION:
        _obs_trace.annotate(
            skip_tier="eager", skip_decision="scan", n_blocks=nb,
            active_blocks=int(na[0]), active_block_fraction=float(frac),
        )
        return None
    _obs_trace.annotate(
        skip_tier="eager", skip_decision="skip", n_blocks=nb,
        active_blocks=int(na[0]), active_block_fraction=float(frac),
    )
    return jnp.asarray(bi), jnp.asarray(na), "static"


def _skip_or_cond(plan, E: int, skip_fn, scan_fn):
    """Commit to the active kernel ('static') or build the runtime choice
    (traced 'auto'): lax.cond on the surviving-block count vs the
    SKIP_BLOCK_FRACTION threshold — both branches return identical values."""
    bi, na, mode = plan
    if mode == "static":
        return skip_fn(bi, na)
    return jax.lax.cond(
        na[0] <= _skip_threshold(E), lambda: skip_fn(bi, na), scan_fn
    )


def streamed_blocks(w, op: str, E: int, blocks, block_skipping: str):
    """The edge blocks :func:`hop` computes over for a traced frontier ``w``
    (int32 scalar): every block, or the surviving ones where the traced
    tier's block list applies ('on', or 'auto' under its threshold)."""
    nb = _active.n_edge_blocks(E)
    if not _may_skip(E, blocks, block_skipping):
        return jnp.int32(nb)
    _, na = _active.active_block_list(
        w, _IDENTITY[op], jnp.asarray(blocks[0]), jnp.asarray(blocks[1])
    )
    if block_skipping == "on":
        return na[0]
    return jnp.where(na[0] <= _skip_threshold(E), na[0], jnp.int32(nb))


def bitunpack(packed, width: int, count: int, use_pallas: bool = True):
    if not use_pallas:
        return ref.bitunpack_ref(jnp.asarray(packed, jnp.uint32), width, count)
    return _bitunpack(jnp.asarray(packed, jnp.uint32), width, count, interpret=_interpret())


def _measure_operands(measure, mdict, m_mode: str):
    if m_mode == "dense":
        return jnp.asarray(measure, jnp.float32), None
    if m_mode in ("packed", "dict"):
        md = jnp.asarray(mdict, jnp.float32) if m_mode == "dict" else None
        return jnp.asarray(measure, jnp.uint32), md
    if m_mode != "none":
        raise ValidationError(f"unknown measure mode {m_mode!r}", m_mode=m_mode)
    return None, None


def hop(weights, src, dst, measure=None, mdict=None, *, n_dst: int,
        src_width: int = 0, dst_width: int = 0, m_mode: str = "none",
        m_width: int = 0, op: str = "sum", blocks=None,
        block_skipping: str = "off", label: str = ""):
    """One hop through the Pallas kernel over a ``[B, n_src]`` frontier.
    ``src``/``dst`` are dense int32 ids or BCA words (``*_width`` > 0), in
    any edge order; ``blocks`` is the (src_min, src_max) skip metadata of
    src-sorted edges, or None. Every public hop entry below lands here."""
    w = jnp.asarray(weights, jnp.float32)
    s = jnp.asarray(src, jnp.uint32 if src_width else jnp.int32)
    d = jnp.asarray(dst, jnp.uint32 if dst_width else jnp.int32)
    m, md = _measure_operands(measure, mdict, m_mode)
    kw = dict(
        n_dst=n_dst, src_width=src_width, dst_width=dst_width, m_mode=m_mode,
        m_width=m_width, op=op, skip_chunks=block_skipping != "off",
        interpret=_interpret(), label=label,
    )
    scan = lambda: _fragment_hop(w, s, d, m, md, None, None, **kw)
    E = n_edges(s, src_width, d, dst_width)
    plan = _plan_skip(w, op, E, blocks, block_skipping)
    if plan is None:
        return scan()
    return _skip_or_cond(
        plan, E, lambda bi, na: _fragment_hop(w, s, d, m, md, bi, na, **kw), scan,
    )


def fragment_spmv(weights, src_ids, dst_ids, measures, n_dst: int,
                  op: str = "sum", use_pallas: bool = True,
                  blocks=None, block_skipping: str = "off", label: str = ""):
    """Single-query hop ``y[dst] ⊕= w[src] ⊗ m``: the batched hop with a
    batch of one."""
    w = jnp.asarray(weights, jnp.float32)
    s = jnp.asarray(src_ids, jnp.int32)
    d = jnp.asarray(dst_ids, jnp.int32)
    m = jnp.asarray(measures, jnp.float32)
    if not use_pallas:
        return ref.fragment_spmv_ref(w, s, d, m, n_dst, op=op)
    _faults.fire("ops.fragment_spmv", op=op, n_dst=n_dst)
    return hop(w[None], s, d, m, n_dst=n_dst, m_mode="dense", op=op,
               blocks=blocks, block_skipping=block_skipping, label=label)[0]


def fragment_spmm(weights, src_ids, dst_ids, measures, n_dst: int,
                  op: str = "sum", use_pallas: bool = True,
                  blocks=None, block_skipping: str = "off", label: str = ""):
    """Batched multi-query hop: ``Y[b, dst] ⊕= W[b, src] ⊗ m`` with one edge
    stream serving all B frontier rows. ``measures`` may be [E] (shared) or
    [B, E] (per-row, e.g. a seed-scalar-dependent measure expression):
    per-row streams have no single-pass formulation and stay on XLA by
    design, a vmap'd segment-combine (``explain()`` lists such hops)."""
    w = jnp.asarray(weights, jnp.float32)
    s = jnp.asarray(src_ids, jnp.int32)
    d = jnp.asarray(dst_ids, jnp.int32)
    m = jnp.asarray(measures, jnp.float32)
    if m.ndim == 2 or not use_pallas:
        return ref.fragment_spmm_ref(w, s, d, m, n_dst, op=op)
    _faults.fire("ops.fragment_spmm", op=op, n_dst=n_dst)
    return hop(w, s, d, m, n_dst=n_dst, m_mode="dense", op=op,
               blocks=blocks, block_skipping=block_skipping, label=label)


def fragment_spmm_packed(weights, src_ids, dst, measure=None, mdict=None, *,
                         n_dst: int, dst_width: int = 0, m_mode: str = "none",
                         m_width: int = 0, op: str = "sum",
                         use_pallas: bool = True,
                         blocks=None, block_skipping: str = "off",
                         src_width: int = 0, label: str = ""):
    """Decode-fused batched hop: packed src/dst/measure word streams decode
    once per 4096-edge block in VMEM and serve all B frontier rows."""
    if not use_pallas:
        if src_width:
            src_ids = ref.bitunpack_ref(
                jnp.asarray(src_ids, jnp.uint32), src_width,
                n_edges(src_ids, src_width, dst, dst_width),
            )
        return ref.fragment_spmm_packed_ref(
            jnp.asarray(weights, jnp.float32), jnp.asarray(src_ids, jnp.int32),
            jnp.asarray(dst, jnp.uint32 if dst_width else jnp.int32),
            *_measure_operands(measure, mdict, m_mode), n_dst,
            dst_width=dst_width, m_mode=m_mode, m_width=m_width, op=op,
        )
    _faults.fire("ops.fragment_spmm_packed", op=op, n_dst=n_dst)
    return hop(weights, src_ids, dst, measure, mdict, n_dst=n_dst,
               src_width=src_width, dst_width=dst_width, m_mode=m_mode,
               m_width=m_width, op=op, blocks=blocks,
               block_skipping=block_skipping, label=label)


def fragment_spmv_packed(weights, src_ids, dst, measure=None, mdict=None, *,
                         n_dst: int, dst_width: int = 0, m_mode: str = "none",
                         m_width: int = 0, op: str = "sum",
                         use_pallas: bool = True,
                         blocks=None, block_skipping: str = "off",
                         src_width: int = 0, label: str = ""):
    """Decode-fused single-query hop (the batched one with a batch of one)."""
    w = jnp.asarray(weights, jnp.float32)
    if not use_pallas:
        return fragment_spmm_packed(
            w[None], src_ids, dst, measure, mdict, n_dst=n_dst,
            dst_width=dst_width, m_mode=m_mode, m_width=m_width, op=op,
            use_pallas=False, src_width=src_width,
        )[0]
    _faults.fire("ops.fragment_spmv_packed", op=op, n_dst=n_dst)
    return hop(w[None], src_ids, dst, measure, mdict, n_dst=n_dst,
               src_width=src_width, dst_width=dst_width, m_mode=m_mode,
               m_width=m_width, op=op, blocks=blocks,
               block_skipping=block_skipping, label=label)[0]


# ---------------------------------------------------------------------------
# Pipelined 2-hop fused dispatch (kernels/fragment_spmv_fused.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(eq=False)
class FusedHopOperands:
    """One hop's streams for the fused entries, in any edge order (the
    executor passes destination-sorted ones). The frontier is *not* here:
    hop1 reads the caller's ``weights``, hop2 reads the VMEM scratch."""

    src_ids: Any
    dst: Any
    measure: Any = None
    mdict: Any = None
    n_dst: int = 0
    dst_width: int = 0
    m_mode: str = "none"
    m_width: int = 0
    src_width: int = 0

    def streams(self):
        """(src, dst, measure, mdict) coerced for the fused kernel."""
        s = jnp.asarray(self.src_ids, jnp.uint32 if self.src_width else jnp.int32)
        d = jnp.asarray(self.dst, jnp.uint32 if self.dst_width else jnp.int32)
        return (s, d, *_measure_operands(self.measure, self.mdict, self.m_mode))

    def modes(self) -> tuple:
        return (self.src_width, self.dst_width, self.m_mode, self.m_width)

    def n_edges(self) -> int:
        return n_edges(self.src_ids, self.src_width, self.dst, self.dst_width)


def _fusion_unfusable(fusion: str, n_mid: int) -> bool:
    """Whether a region runs unfused: 'off', or 'auto' when the resident
    ``(8, n_mid)`` intermediate group exceeds FUSED_VMEM_BUDGET_BYTES."""
    if fusion not in FUSION_MODES:
        raise ValidationError(
            f"unknown fusion mode {fusion!r}", fusion=fusion, valid=FUSION_MODES,
        )
    if fusion == "off":
        return True
    if fusion == "on":
        return False
    return 4 * GROUP_ROWS * padded(n_mid) > FUSED_VMEM_BUDGET_BYTES


def fuses(hop1: FusedHopOperands, hop2: FusedHopOperands | None,
          fusion: str, use_pallas: bool) -> bool:
    """Whether a region runs the single-pass fused kernel; otherwise its
    member hops run through the unfused kernels (or XLA without Pallas)."""
    return (
        use_pallas
        and not _fusion_unfusable(fusion, hop1.n_dst)
        and hop1.n_edges() > 0
        and (hop2 is None or hop2.n_edges() > 0)
    )


def _compose_unfused(packed_fn, weights, hop1, hop2, mid_mask,
                     mid_binarize: bool, op: str, use_pallas: bool,
                     block_skipping: str, labels: tuple):
    """The member hops through the unfused kernels (fusion off / VMEM budget
    exceeded / empty relation) — the reference semantics the fused kernel must
    match bit-for-bit."""
    u = packed_fn(
        weights, hop1.src_ids, hop1.dst, hop1.measure, hop1.mdict,
        n_dst=hop1.n_dst, dst_width=hop1.dst_width, m_mode=hop1.m_mode,
        m_width=hop1.m_width, op=op, use_pallas=use_pallas,
        block_skipping=block_skipping, src_width=hop1.src_width,
        label=labels[0],
    )
    if mid_mask is not None:
        keep = mid_mask[None, :] if u.ndim == 2 else mid_mask
        u = _fused_apply_mask(u, keep, op)
    if hop2 is None:
        return u
    if mid_binarize:
        u = _fused_binarize(u, op)
    return packed_fn(
        u, hop2.src_ids, hop2.dst, hop2.measure, hop2.mdict,
        n_dst=hop2.n_dst, dst_width=hop2.dst_width, m_mode=hop2.m_mode,
        m_width=hop2.m_width, op=op, use_pallas=use_pallas,
        block_skipping=block_skipping, src_width=hop2.src_width,
        label=labels[1],
    )


def _fused_dispatch(batched: bool, weights, hop1, hop2, mid_mask, *, op,
                    mid_binarize, use_pallas, fusion, block_skipping, labels):
    _check_skip_mode(block_skipping)
    w = jnp.asarray(weights, jnp.float32)
    mm = jnp.asarray(mid_mask, jnp.float32) if mid_mask is not None else None
    n_mid = hop1.n_dst
    n_dst = hop2.n_dst if hop2 is not None else hop1.n_dst
    packed_fn = fragment_spmm_packed if batched else fragment_spmv_packed
    labels = tuple(labels) + ("",) * (2 - len(labels))
    if not fuses(hop1, hop2, fusion, use_pallas):
        return _compose_unfused(
            packed_fn, w, hop1, hop2, mm, mid_binarize, op,
            use_pallas, block_skipping, labels,
        )
    site = "ops.fragment_spmm_fused" if batched else "ops.fragment_spmv_fused"
    _faults.fire(site, op=op, n_dst=n_dst)
    _obs_trace.annotate(fused=True, fused_hops=2 if hop2 is not None else 1)
    out = _fragment_hops_fused(
        w if batched else w[None],
        hop1.streams(), hop2.streams() if hop2 is not None else None, mm,
        n_mid=n_mid, n_dst=n_dst, cfg1_modes=hop1.modes(),
        cfg2_modes=hop2.modes() if hop2 is not None else None,
        op=op, mid_binarize=mid_binarize and hop2 is not None,
        skip_chunks=block_skipping != "off", interpret=_interpret(),
        label="+".join(x for x in labels if x),
    )
    return out if batched else out[0]


def fragment_spmv_fused(weights, hop1: FusedHopOperands,
                        hop2: FusedHopOperands | None = None, mid_mask=None,
                        *, op: str = "sum", mid_binarize: bool = False,
                        use_pallas: bool = True, fusion: str = "auto",
                        block_skipping: str = "off", labels: tuple = ()):
    """Pipelined fused region: hop1 → in-register mask/binarize → hop2 in one
    kernel pass, the intermediate frontier resident in VMEM scratch
    (``hop2=None`` ⇒ degenerate 1-hop+filter region). Bit-identical to the
    unfused two-call composition on every op × encoding × skip mode."""
    return _fused_dispatch(
        False, weights, hop1, hop2, mid_mask, op=op,
        mid_binarize=mid_binarize, use_pallas=use_pallas, fusion=fusion,
        block_skipping=block_skipping, labels=labels,
    )


def fragment_spmm_fused(weights, hop1: FusedHopOperands,
                        hop2: FusedHopOperands | None = None, mid_mask=None,
                        *, op: str = "sum", mid_binarize: bool = False,
                        use_pallas: bool = True, fusion: str = "auto",
                        block_skipping: str = "off", labels: tuple = ()):
    """Batched pipelined region: B queries share the single fused pass, the
    ``[B, n_mid]`` intermediate resident in VMEM scratch."""
    return _fused_dispatch(
        True, weights, hop1, hop2, mid_mask, op=op,
        mid_binarize=mid_binarize, use_pallas=use_pallas, fusion=fusion,
        block_skipping=block_skipping, labels=labels,
    )


def bitmap_and(a, b, use_pallas: bool = True):
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    if not use_pallas:
        return ref.bitmap_and_ref(a, b)
    return _bitmap_and(a, b, interpret=_interpret())


def bitmap_and_popcount(a, b, use_pallas: bool = True):
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    if not use_pallas:
        return ref.bitmap_and_popcount_ref(a, b)
    return _bitmap_and_popcount(a, b, interpret=_interpret())
