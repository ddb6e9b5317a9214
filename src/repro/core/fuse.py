"""IR fusion pass: group adjacent HopOp chains into pipelined regions.

GQ-Fast's execution model is *fully pipelined* — intermediate results never
materialize between operators. The physical IR from :mod:`.lower` is a flat op
list, and the frontier interpreter used to write a full ``[n_entity]`` frontier
vector to HBM after every HopOp. This pass rewrites the plan so that adjacent
hops (plus any interleaved constant-mask EntityFilterOps and the trailing
GroupOp) become one :class:`repro.core.lower.FusedHopOp` region, which the
frontier/batched interpreters execute in a single Pallas grid pass
(:mod:`repro.kernels.fragment_spmv_fused`): hop1 accumulates into a VMEM
scratch buffer, the mid mask is applied in-register, hop2 streams its edge
blocks against the VMEM-resident frontier.

Region formation rules (DESIGN.md §Pipelined fusion):

  * a region opens at a HopOp and absorbs at most TWO hops (the kernel is a
    two-phase grid; longer chains become back-to-back regions);
  * EntityFilterOps join only if they are pure constant masks — a ``factor``
    expression or parameter-dependent conditions end the region (their values
    are not known at fuse time);
  * DegreeFilterOp always ends a region (it reads the *pre-hop* frontier);
  * the final GroupOp joins when it immediately follows the region, so the
    whole tail of the plan is one span in profiles;
  * a region must contain either two hops or one hop plus at least one filter
    (a bare single hop gains nothing from fusion and stays as-is);
  * SeedOp sub-programs (mask seeds) are fused recursively.

Whether a formed region runs fused is decided at dispatch
(``kernels/ops.py``): ``fusion='auto'`` runs it unfused when the resident
``(8, n_mid)`` intermediate would exceed ``FUSED_VMEM_BUDGET_BYTES``.
Both phases stream every edge block of their destination-sorted edges;
skipping is by active frontier chunk, as in the unfused kernel.
"""
from __future__ import annotations

import dataclasses

from .lower import (
    EntityFilterOp,
    FusedHopOp,
    GroupOp,
    HopOp,
    PhysicalPlan,
    SeedOp,
)


def _pure_mask_filter(op) -> bool:
    return (
        isinstance(op, EntityFilterOp)
        and op.factor is None
        and not op.param_conds
    )


def _form_regions(ops: tuple) -> tuple:
    out: list = []
    i = 0
    n = len(ops)
    while i < n:
        op = ops[i]
        if not isinstance(op, HopOp):
            out.append(op)
            i += 1
            continue
        members: list = [op]
        j = i + 1
        while j < n and _pure_mask_filter(ops[j]):
            members.append(ops[j])
            j += 1
        second = None
        if j < n and isinstance(ops[j], HopOp):
            second = ops[j]
            members.append(second)
            j += 1
        if len(members) == 1:  # bare hop: nothing to pipeline
            out.append(op)
            i += 1
            continue
        if j < n and isinstance(ops[j], GroupOp) and j == n - 1:
            members.append(ops[j])
            j += 1
        n_mid = op.dom_dst
        out.append(FusedHopOp(tuple(members), n_mid))
        i = j
    return tuple(out)


def fuse_plan(phys: PhysicalPlan) -> PhysicalPlan:
    """Return a plan with fusable op runs collapsed into FusedHopOp regions
    (idempotent; plans with no fusable run come back unchanged)."""
    ops = []
    for op in phys.ops:
        if isinstance(op, SeedOp) and op.programs:
            op = dataclasses.replace(
                op, programs=tuple(fuse_plan(p) for p in op.programs)
            )
        ops.append(op)
    fused = _form_regions(tuple(ops))
    return dataclasses.replace(phys, ops=fused)


def unfuse_plan(phys: PhysicalPlan) -> PhysicalPlan:
    """Inverse of :func:`fuse_plan`: expand every region back to its member
    ops (the robustness ladder's ``unfused`` rung and the scan/xla rungs
    compile against this)."""
    ops: list = []
    for op in phys.ops:
        if isinstance(op, SeedOp) and op.programs:
            op = dataclasses.replace(
                op, programs=tuple(unfuse_plan(p) for p in op.programs)
            )
        if isinstance(op, FusedHopOp):
            ops.extend(op.members)
        else:
            ops.append(op)
    return dataclasses.replace(phys, ops=tuple(ops))


def has_fused(phys: PhysicalPlan) -> bool:
    return any(isinstance(op, FusedHopOp) for op in phys.ops) or any(
        isinstance(op, SeedOp) and any(has_fused(p) for p in op.programs)
        for op in phys.ops
    )


def fusion_groups(phys: PhysicalPlan) -> list[str]:
    """One line per fused region, for ``explain()``."""
    groups = []
    for op in phys.ops:
        if isinstance(op, FusedHopOp):
            sigs = dataclasses.replace(phys, ops=op.members).op_signature()
            groups.append(" + ".join(sigs))
    return groups
