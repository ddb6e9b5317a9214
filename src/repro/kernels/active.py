"""Frontier-sparsity metadata: per-block src ranges and active-block lists.

GQ-Fast's selective-query win (paper §4-5) comes from touching only the index
*fragments* reachable from the active sources. The hop kernel
(:mod:`.fragment_spmv`) streams ``EDGE_BLOCK``-edge blocks; for source-sorted
edges this module restores fragment-level selectivity at block granularity
(the served path, whose edge blocks follow the destination, skips by
frontier chunk instead — see :mod:`.ops`):

  * :func:`block_ranges` — build-time (host, numpy): for each EDGE_BLOCK-sized
    block of the CSR-ordered edge arrays, its ``[src_min, src_max]`` source-id
    range. Edges are sorted by src, so block ranges are a monotone partition of
    the CSR offsets; any frontier whose support misses a block's range can skip
    that block entirely (every edge in it carries ⊕-identity weight).
  * :func:`active_flags` / :func:`compact_blocks` — per-hop (traced): from the
    frontier's nonzero support, mark blocks whose src range intersects it, and
    compact the surviving block ids into a **fixed-capacity list + count** so
    shapes stay static under jit. The list's tail repeats the last active block
    — a revisited block index costs no new DMA on TPU, and the compute is
    guarded off by the in-kernel ``i < n_active`` predicate.
  * :func:`active_block_list_np` — the eager twin: when the frontier is a
    concrete array (kernel-level callers outside an enclosing jit, e.g. the
    selectivity benchmark), the list is computed in numpy and its capacity
    bucketed to a power of two, so the grid itself shrinks to the surviving
    blocks and recompiles stay bounded at ~log2(n_blocks) per shape.

The same module holds the geometry behind the hop kernel's work counters
(:func:`row_ranges`, :func:`chunk_coverage`): per 128-edge row of a stream,
the range of source chunks its inner gather loop walks — and the pull
stream's within-block edge order that narrows those ranges
(:func:`block_source_order`).

Skipping is *bit-identical* to the full scan for every combine op: a skipped
block's sources all carry the ⊕-identity, so its per-block contribution is the
⊕-identity vector and ``combine(acc, identity) == acc`` exactly (0 for sum,
±∞ for min/max, 0 for bool). Conversely an active block whose range merely
*straddles* the support (a gap block) contributes identity edge products — the
same values the scan computes.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .params import EDGE_BLOCK, LANES

#: Runtime "auto" heuristic: engage skipping only while the surviving-block
#: fraction is at most this — above it the scan's simpler schedule wins and
#: the active-list work is pure overhead (the ≤1.1× full-selectivity budget).
SKIP_BLOCK_FRACTION = 0.25


def n_edge_blocks(E: int) -> int:
    """Blocks the streaming kernels use for an E-edge index (≥ 1)."""
    return max(1, -(-E // EDGE_BLOCK))


def block_ranges(src_ids) -> tuple[np.ndarray, np.ndarray]:
    """Per-block ``[src_min, src_max]`` over EDGE_BLOCK-sized blocks of the
    CSR-ordered (src-sorted) edge array. Host/numpy — runs once at
    ``build_device_db`` time. An empty relation gets the 1-entry sentinel
    ``([0], [-1])`` whose range intersects no support."""
    src = np.asarray(src_ids)
    E = src.shape[0]
    if E == 0:
        return np.zeros(1, np.int32), np.full(1, -1, np.int32)
    nb = n_edge_blocks(E)
    starts = np.arange(nb, dtype=np.int64) * EDGE_BLOCK
    ends = np.minimum(starts + EDGE_BLOCK, E) - 1
    return src[starts].astype(np.int32), src[ends].astype(np.int32)


def support_mask(w, zero: float):
    """Nonzero support of a frontier over the source domain: ``w != 0̄`` for a
    ``[n_src]`` vector; the batched ``[B, n_src]`` matrix reduces with ∨ over
    rows (one shared block list serves all B queries — a block survives when
    *any* query's support intersects it)."""
    nz = w != zero
    if nz.ndim == 2:
        nz = nz.any(axis=0)
    return nz


def active_flags(support, src_min, src_max):
    """bool[n_blocks]: does any supported source fall in ``[src_min, src_max]``?
    One exclusive prefix count over the source domain turns each block test
    into two gathers — O(n_src + n_blocks), no per-block scan."""
    cs = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(support.astype(jnp.int32))]
    )
    return cs[src_max + 1] > cs[src_min]


def compact_blocks(flags):
    """Fixed-capacity compaction: ``(block_idx int32[n_blocks], n_active
    int32[1])`` with the surviving block ids first (ascending — stable argsort
    on the inactive flag) and the tail repeating the last active block, so the
    scalar-prefetch ``index_map`` always names a valid block and inactive grid
    steps re-request the resident one (no new DMA)."""
    nb = flags.shape[0]
    order = jnp.argsort(~flags, stable=True).astype(jnp.int32)
    n_active = jnp.sum(flags).astype(jnp.int32)
    last = order[jnp.maximum(n_active - 1, 0)]
    idx = jnp.where(jnp.arange(nb, dtype=jnp.int32) < n_active, order, last)
    return idx, n_active.reshape(1)


def active_block_list(w, zero: float, src_min, src_max):
    """Traced path: frontier → (block_idx[n_blocks], n_active[1])."""
    return compact_blocks(active_flags(support_mask(w, zero), src_min, src_max))


def bucket_capacity(n: int, nb: int) -> int:
    """Smallest power-of-two ≥ n, capped at nb (and ≥ 1) — the eager path's
    grid size, bucketed so the per-shape compile count stays ~log2(nb)."""
    if n >= nb:
        return nb
    return max(1, min(nb, 1 << (max(1, n) - 1).bit_length()))


def active_block_list_np(support, src_min, src_max):
    """Eager twin of :func:`active_block_list` for concrete frontiers:
    ``(block_idx int32[C], n_active int32[1], active_fraction float)`` with
    ``C = bucket_capacity(n_active, n_blocks)`` — the grid really shrinks."""
    sup = np.asarray(support).astype(np.int64)
    cs = np.concatenate([np.zeros(1, np.int64), np.cumsum(sup)])
    flags = cs[np.asarray(src_max) + 1] > cs[np.asarray(src_min)]
    act = np.flatnonzero(flags).astype(np.int32)
    nb = int(flags.shape[0])
    C = bucket_capacity(int(act.shape[0]), nb)
    idx = np.full(C, act[-1] if act.size else 0, np.int32)
    idx[: act.shape[0]] = act
    n_active = np.asarray([act.shape[0]], np.int32)
    return idx, n_active, act.shape[0] / nb


def row_ranges(src_ids) -> tuple[np.ndarray, np.ndarray]:
    """Per 128-edge row ``[src_min, src_max]`` of an edge stream in the
    order the hop kernel reads it (host/numpy, once at ``build_device_db``
    time, like :func:`block_ranges`). A last, partial row covers its real
    edges only; :func:`chunk_coverage` adds the padding."""
    src = np.asarray(src_ids)
    if src.shape[0] == 0:
        return np.zeros(0, np.int32), np.zeros(0, np.int32)
    starts = np.arange(0, src.shape[0], LANES)
    return (np.minimum.reduceat(src, starts).astype(np.int32),
            np.maximum.reduceat(src, starts).astype(np.int32))


def block_source_order(src_ids) -> np.ndarray:
    """int64[E]: the permutation that sorts each EDGE_BLOCK block of an edge
    stream by source and leaves the blocks where they are (host/numpy, one
    row-wise sort of the ``[blocks, EDGE_BLOCK]`` source keys, in the
    narrowest unsigned type that holds them). The sort is stable, so equal
    sources keep their stream order; in the CSR order of an index keyed on
    the hop's destination that breaks ties by destination. The last block's
    padding keys sort after every source and are cut off."""
    src = np.asarray(src_ids)
    E = src.shape[0]
    if E == 0:
        return np.zeros(0, np.int64)
    nb = n_edge_blocks(E)
    top = int(src.max())
    dtype = next(t for t in (np.uint16, np.uint32, np.uint64)
                 if top < np.iinfo(t).max)
    keys = np.full(nb * EDGE_BLOCK, np.iinfo(dtype).max, dtype)
    keys[:E] = src
    order = np.argsort(keys.reshape(nb, EDGE_BLOCK), axis=1, kind="stable")
    order += (np.arange(nb, dtype=np.int64) * EDGE_BLOCK)[:, None]
    return order.reshape(-1)[:E]


def chunk_coverage(row_min, row_max, E: int, n_src: int) -> np.ndarray:
    """int64[n_src // 128 + 1]: for each 128-lane frontier chunk ``c``, how
    many streamed rows of the hop kernel have ``c`` inside their source-chunk
    range ``[min(s) >> 7, max(s) >> 7]`` — every row of every EDGE_BLOCK
    block, padding rows included (their sources read ``n_src``, the spare
    chunk). The kernel's gather loop runs, per row and batch group, once per
    *active* chunk of that range, so its trip count for a frontier with
    active-chunk flags ``f`` is ``groups · Σ_c f[c] · coverage[c]``."""
    nc = n_src // LANES + 1
    rows = n_edge_blocks(E) * (EDGE_BLOCK // LANES)
    lo = np.full(rows, n_src >> 7, np.int64)
    hi = np.full(rows, n_src >> 7, np.int64)
    real = np.asarray(row_min).shape[0]
    lo[:real] = np.asarray(row_min) >> 7
    hi[:real] = np.asarray(row_max) >> 7
    if real and E % LANES:
        hi[real - 1] = n_src >> 7  # the last row's padding lanes
    diff = np.bincount(lo, minlength=nc + 1) - np.bincount(hi + 1, minlength=nc + 1)
    return np.cumsum(diff[:nc])
