"""Shared block geometry for the hop kernels (single source of truth).

The hop kernel (:mod:`.fragment_spmv`) and the fused two-hop kernel
(:mod:`.fragment_spmv_fused`) stream the edge arrays through VMEM in blocks
of ``EDGE_BLOCK`` edges per grid step, processed as 32 rows of 128 lanes.
The value is load-bearing for the packed streams: EDGE_BLOCK = 4096 values
and 4096·width ≡ 0 (mod 32) for every width 1–32, so each block starts and
ends word-aligned in the BCA uint32 word stream and the packed block is
exactly ``128·width`` words — a static BlockSpec, no halo.
"""
from __future__ import annotations

EDGE_BLOCK = 4096  # edges per grid step; 32 rows of 128 lanes
LANES = 128  # edges per row; frontier entries per chunk

#: Upper bound on the scoped-VMEM request of one hop kernel. The kernels keep
#: ``(8, n)`` frontier and accumulator groups resident; the compiler's default
#: scoped limit (16 MiB on v5e) refuses an ``(8, 2^20)`` f32 group, so each
#: call asks for what its groups need, capped here below v5e's 128 MiB.
VMEM_LIMIT_BYTES = 110 * 2**20

#: VMEM budget for the fused 2-hop kernels' resident intermediate frontier
#: (:mod:`.fragment_spmv_fused`): one ``(8, n_mid)`` f32 group in a VMEM
#: scratch buffer for the whole grid. ``fusion="auto"`` falls back to the
#: unfused two-kernel path when ``4 · 8 · n_mid`` exceeds this, leaving room
#: for the frontier and output groups under ``VMEM_LIMIT_BYTES``.
FUSED_VMEM_BUDGET_BYTES = 48 * 2**20
