"""Pallas TPU kernel: one relationship hop, ``Y[b, dst] ⊕= W[b, src] ⊗ m``.

Every ⋈/⋉+γ hop lowers to this frontier SpMM (DESIGN.md §4). One kernel
serves every caller: a single query is a batch of one, a full scan is the
block list that names every block, and dense and BCA bit-packed streams
differ only in how a block is staged. The combine op ⊕ is a parameter
(``'sum' | 'min' | 'max' | 'bool'``), matching the executor's semiring
plug-in point.

Layout (what the TPU compiler sees):

  * Frontier rows are padded to groups of 8 (one sublane tile); the grid is
    ``(batch groups, edge blocks)``. The ``[8, n_src]`` frontier group and
    the ``[8, n_dst]`` accumulator group stay resident in VMEM for the whole
    pass over the edge blocks. Both domains are padded to whole 128-lane
    chunks plus one spare chunk, so the padding edges' source (``n_src``)
    always reads the ⊕-identity.
  * Edge streams arrive as ``[n_blocks, 1, L]`` arrays: one ``(1, 4096)``
    row of int32/f32 per block, or ``(1, 128·width)`` uint32 BCA words that
    the kernel decodes into a row scratch (:func:`decode_row`).
  * A block is processed as 32 rows of 128 edges. Gather: for each row, the
    frontier chunks that hold its sources *and* are active (a
    scalar-prefetched compacted chunk list plus prefix counts) are read as
    ``(8, 128)`` tiles and the row picks its values with an in-vreg lane
    gather. Scatter-⊕: for each 128-lane destination chunk the row touches,
    a one-hot ``(128 dst, 128 edge)`` mask turns the scatter into an MXU
    product (sum, bool) or a masked lane reduction (min, max), combined into
    the resident accumulator. A row whose products are all ⊕-identity skips
    the scatter.

Per-edge cost therefore follows the width of a row's source and destination
ranges, not the domain sizes. Edges sorted by destination (the index keyed
on the hop's destination — the executor's pull layout) give each row one or
two destination chunks; edges sorted by source (CSR) give narrow source
ranges and, with a sparse frontier, few non-identity rows.

Exactness: the gather is a select, so it is exact. The sum scatter splits
each f32 product into three bf16 terms whose one-hot products are exact in
f32, so the result differs from a sequential f32 sum only by accumulation
order. Results never depend on the batch size: every dot has the shape
``(8, 128) × (128, 128)``.

Padding edges point src at ``n_src`` (an identity column), carry measure 0
and repeat the last real destination.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .params import EDGE_BLOCK, LANES, VMEM_LIMIT_BYTES

GROUP_ROWS = 8  # frontier rows per batch group: one sublane tile
ROWS_PER_BLOCK = EDGE_BLOCK // LANES

# ⊕-identity per combine op ("no path reaches this entity")
IDENTITY = {
    "sum": 0.0,
    "min": float("inf"),
    "max": float("-inf"),
    "bool": 0.0,
}

def n_chunks(n: int) -> int:
    """128-lane chunks of a padded domain: always one spare past ``n``."""
    return n // LANES + 1


def padded(n: int) -> int:
    return n_chunks(n) * LANES


def group_pad(b: int) -> int:
    return -(-max(b, 1) // GROUP_ROWS) * GROUP_ROWS


@dataclasses.dataclass(frozen=True)
class HopCfg:
    """Static shape of one hop's operands. ``*_width`` 0 means a dense int32
    stream, otherwise BCA words of that width."""

    n_src: int
    n_dst: int
    src_width: int = 0
    dst_width: int = 0
    m_mode: str = "none"
    m_width: int = 0
    dict_len: int = 0

    def n_operands(self) -> int:
        return 2 + (self.m_mode != "none") + (self.m_mode == "dict")


# ---------------------------------------------------------------------------
# Operand preparation (outside the kernel, XLA)
# ---------------------------------------------------------------------------


def pad_frontier(w, n_src: int, op: str):
    """``[B, n_src]`` → ``[group_pad(B), padded(n_src)]`` with ⊕-identity."""
    B = w.shape[0]
    return jnp.pad(
        w, ((0, group_pad(B) - B), (0, padded(n_src) - n_src)),
        constant_values=IDENTITY[op],
    )


def chunk_lists(w_padded, op: str, skip: bool):
    """Scalar-prefetch chunk metadata for a padded frontier: the compacted
    list of chunks holding any non-identity value (every chunk when ``skip``
    is off) and ``cnt[c]`` = active chunks before chunk ``c``."""
    nc = w_padded.shape[1] // LANES
    if skip:
        flags = (w_padded != IDENTITY[op]).reshape(-1, nc, LANES).any(axis=(0, 2))
    else:
        flags = jnp.ones((nc,), bool)
    order = jnp.argsort(~flags, stable=True).astype(jnp.int32)
    cnt = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(flags.astype(jnp.int32))]
    )
    return order, cnt


#: Chunks summed per partial sum of :func:`gather_trips`: with 16-bit halves
#: of the coverage, every partial sum stays below 2**31.
TRIP_SEGMENT = 1 << 15


def chunk_flags(w, op: str, skip: bool):
    """bool[n_chunks(n_src)]: the chunks :func:`chunk_lists` marks active,
    from the unpadded ``[B, n_src]`` frontier (the spare chunk holds only
    ⊕-identity padding, so it is active only when ``skip`` is off)."""
    n_src = w.shape[-1]
    nc = n_chunks(n_src)
    if not skip:
        return jnp.ones((nc,), bool)
    nz = jnp.pad((w != IDENTITY[op]).any(axis=0), (0, nc * LANES - n_src))
    return nz.reshape(nc, LANES).any(axis=1)


def gather_trips(flags, coverage) -> jnp.ndarray:
    """``Σ_c flags[c] · coverage[c]`` — the gather-loop trips of
    :func:`hop_block` per batch group, given the stream's
    ``kernels.active.chunk_coverage`` — as exact int32 partial sums: the
    coverage splits into 16-bit halves and the chunks into segments of
    :data:`TRIP_SEGMENT`, so ``int32[2·S]`` holds S low sums then S high
    sums, and the count is ``Σ low + 2**16 · Σ high``."""
    nc = flags.shape[0]
    S = -(-nc // TRIP_SEGMENT)
    cov = np.zeros(S * TRIP_SEGMENT, np.int32)  # a row count: below 2**31
    cov[:nc] = coverage
    cov = jnp.asarray(cov.reshape(S, TRIP_SEGMENT))
    f = jnp.pad(flags.astype(jnp.int32), (0, S * TRIP_SEGMENT - nc))
    f = f.reshape(S, TRIP_SEGMENT)
    return jnp.concatenate([(f * (cov & 0xFFFF)).sum(axis=1),
                            (f * (cov >> 16)).sum(axis=1)])


def _edge_rows(x, n_blocks: int, fill, dtype):
    x = jnp.asarray(x, dtype)
    pad = n_blocks * EDGE_BLOCK - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, dtype)])
    return x.reshape(n_blocks, 1, EDGE_BLOCK)


def _word_rows(words, width: int, n_blocks: int):
    words = jnp.asarray(words, jnp.uint32)
    need = n_blocks * EDGE_BLOCK * width // 32
    if words.shape[0] < need:
        words = jnp.concatenate(
            [words, jnp.zeros(need - words.shape[0], jnp.uint32)]
        )
    return words[:need].reshape(n_blocks, 1, EDGE_BLOCK * width // 32)


def edge_operands(cfg: HopCfg, E: int, src, dst, measure, mdict):
    """The hop's stream operands in kernel layout. Dense src pads with
    ``n_src`` (an identity column), dense dst with its last value (keeps the
    final row's destination range narrow), packed streams with zero words —
    a packed src's pad values are masked to ``n_src`` in the kernel
    (:class:`BlockStreams`)."""
    nb = max(1, -(-E // EDGE_BLOCK))
    ops = []
    if cfg.src_width:
        ops.append(_word_rows(src, cfg.src_width, nb))
    else:
        ops.append(_edge_rows(src, nb, cfg.n_src, jnp.int32))
    if cfg.dst_width:
        ops.append(_word_rows(dst, cfg.dst_width, nb))
    else:
        d = jnp.asarray(dst, jnp.int32)
        ops.append(_edge_rows(d, nb, d[E - 1] if E else 0, jnp.int32))
    if cfg.m_mode == "dense":
        ops.append(_edge_rows(measure, nb, 0.0, jnp.float32))
    elif cfg.m_mode in ("packed", "dict"):
        ops.append(_word_rows(measure, cfg.m_width, nb))
        if cfg.m_mode == "dict":
            md = jnp.asarray(mdict, jnp.float32).reshape(1, -1)
            ops.append(jnp.pad(md, ((0, 0), (0, padded(md.shape[1]) - md.shape[1]))))
    elif cfg.m_mode != "none":
        raise ValueError(f"unknown measure mode {cfg.m_mode!r}")
    return ops, nb


# ---------------------------------------------------------------------------
# In-kernel helpers
# ---------------------------------------------------------------------------


def _lane_gather(x, idx):
    """``x[0, idx]`` for ``(1, 128)`` rows: the in-vreg lane gather takes
    full ``(8, 128)`` tiles, so the row is broadcast and one sublane kept."""
    full = (GROUP_ROWS, LANES)
    g = jnp.take_along_axis(
        jnp.broadcast_to(x, full), jnp.broadcast_to(idx, full), axis=1
    )
    return g[0:1]


def decode_row(words_ref, width: int, out_ref) -> None:
    """BCA words ``(1, 128·width)`` → ``(1, EDGE_BLOCK)`` int32 values in
    ``out_ref``, one 128-value row per loop step. Row ``k`` starts at word
    ``4·k·width`` and spans at most ``4·width + 1`` words, so both words of
    every value lie in a 128-aligned window of two chunks: two in-vreg lane
    gathers per word, selected by chunk."""
    n_words = EDGE_BLOCK * width // 32
    span = min(n_words, 2 * LANES)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    bit = lane * width
    off = (bit & 31).astype(jnp.uint32)
    mask = jnp.uint32((1 << width) - 1) if width < 32 else jnp.uint32(0xFFFFFFFF)

    def fetch(win, idx):
        g = _lane_gather(win[0], idx & (LANES - 1))
        if span == LANES:
            return g
        return jnp.where(idx < LANES, g, _lane_gather(win[1], idx & (LANES - 1)))

    def row(k, carry):
        first = 4 * width * k  # word holding value 128·k's low bits
        a = jnp.minimum((first // LANES) * LANES, n_words - span)
        win = [
            words_ref[:, pl.ds(pl.multiple_of(a + c, LANES), LANES)]
            for c in range(0, span, LANES)
        ]
        lo_i = (bit >> 5) + (first - a)
        lo = fetch(win, lo_i)
        hi = fetch(win, jnp.minimum(lo_i + 1, span - 1))
        v = jnp.where(off == 0, lo, (lo >> off) | (hi << ((32 - off) & 31)))
        out_ref[:, pl.ds(pl.multiple_of(k * LANES, LANES), LANES)] = (
            v & mask
        ).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, ROWS_PER_BLOCK, row, 0)


def _dict_lookup(dict_ref, idx, dict_len: int):
    """``mdict[idx]`` for a ``(1, 128)`` index row: lane gather per
    128-entry dictionary chunk."""
    lo, hi = idx & (LANES - 1), idx >> 7

    def chunk(c, m):
        g = _lane_gather(
            dict_ref[:, pl.ds(pl.multiple_of(c * LANES, LANES), LANES)], lo
        )
        return jnp.where(hi == c, g, m)

    return jax.lax.fori_loop(
        0, n_chunks(dict_len), chunk, jnp.zeros(idx.shape, jnp.float32)
    )


def edge_product(ws, m, op: str):
    """``w[src] ⊗ m`` with the identity guard non-sum lattices need
    (∞·0 = NaN)."""
    zero = IDENTITY[op]
    if op == "sum":
        return ws * m
    if op == "bool":
        return ((ws > 0) & (m != 0)).astype(jnp.float32)
    return jnp.where(ws == zero, zero, ws * m)


def combine(a, b, op: str):
    if op == "sum":
        return a + b
    if op == "min":
        return jnp.minimum(a, b)
    return jnp.maximum(a, b)


def _nt_dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def scatter_chunk(prod, d, base, op: str):
    """One row's contribution to the 128 destinations ``[base, base+128)``:
    ``prod`` is ``(8, 128 edges)``, ``d`` the row's ``(1, 128)`` dst ids."""
    hit = jnp.broadcast_to(d, (LANES, LANES)) == (
        jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0) + base
    )  # [dst lane j, edge e]
    if op in ("min", "max"):
        zero = IDENTITY[op]
        vals = jnp.where(hit[None], prod[:, None, :], zero)
        return vals.min(axis=2) if op == "min" else vals.max(axis=2)
    oh = hit.astype(jnp.bfloat16)
    if op == "bool":
        return (_nt_dot(prod.astype(jnp.bfloat16), oh) > 0).astype(jnp.float32)
    # three exact bf16 terms: p = p1 + p2 + p3
    p1 = prod.astype(jnp.bfloat16)
    r1 = prod - p1.astype(jnp.float32)
    p2 = r1.astype(jnp.bfloat16)
    p3 = (r1 - p2.astype(jnp.float32)).astype(jnp.bfloat16)
    return _nt_dot(p1, oh) + _nt_dot(p2, oh) + _nt_dot(p3, oh)


class BlockStreams:
    """Row access to one edge block: dense streams straight from the input
    refs, packed ones from the row scratch they were decoded into."""

    def __init__(self, cfg: HopCfg, in_refs, scratch, n_edges: int, blk):
        self.cfg = cfg
        self.in_refs = in_refs
        self.scratch = scratch  # (src_s, dst_s, m_s)
        self.n_edges = n_edges  # static; masks a packed src's pad values
        self.blk = blk  # this step's edge block id

    def stage(self) -> None:
        cfg, (src, dst, *rest), (src_s, dst_s, m_s) = (
            self.cfg, self.in_refs, self.scratch
        )
        if cfg.src_width:
            decode_row(src, cfg.src_width, src_s)
        if cfg.dst_width:
            decode_row(dst, cfg.dst_width, dst_s)
        if cfg.m_mode in ("packed", "dict"):
            decode_row(rest[0], cfg.m_width, m_s)

    def src(self, off):
        if not self.cfg.src_width:
            return self.in_refs[0][:, pl.ds(off, LANES)]
        s = self.scratch[0][:, pl.ds(off, LANES)]
        e = self.blk * EDGE_BLOCK + off + jax.lax.broadcasted_iota(
            jnp.int32, (1, LANES), 1
        )
        return jnp.where(e < self.n_edges, s, self.cfg.n_src)

    def dst(self, off):
        ref = self.scratch[1] if self.cfg.dst_width else self.in_refs[1]
        return ref[:, pl.ds(off, LANES)]

    def measure(self, off):
        cfg = self.cfg
        if cfg.m_mode == "none":
            return jnp.ones((1, LANES), jnp.float32)
        if cfg.m_mode == "dense":
            return self.in_refs[2][:, pl.ds(off, LANES)]
        idx = self.scratch[2][:, pl.ds(off, LANES)]
        if cfg.m_mode == "dict":
            return _dict_lookup(self.in_refs[3], idx, cfg.dict_len)
        return idx.astype(jnp.float32)


def hop_block(streams: BlockStreams, w_ref, clist, ccnt, out_ref, op: str):
    """Stream one edge block: gather from the ``(8, ·)`` frontier ref over
    the active chunks, scatter-⊕ into the ``(8, ·)`` accumulator ref.
    ``clist``/``ccnt`` are SMEM refs (prefetched or scratch)."""
    zero = jnp.float32(IDENTITY[op])
    streams.stage()

    def row(r, carry):
        off = pl.multiple_of(r * LANES, LANES)
        s = streams.src(off)
        j0 = ccnt[jnp.min(s) >> 7]
        j1 = ccnt[(jnp.max(s) >> 7) + 1]
        sb = jnp.broadcast_to(s, (GROUP_ROWS, LANES))
        s_hi, s_lo = sb >> 7, sb & (LANES - 1)

        def gather(j, acc):
            c = clist[j]
            x = w_ref[:, pl.ds(pl.multiple_of(c * LANES, LANES), LANES)]
            return jnp.where(
                s_hi == c, jnp.take_along_axis(x, s_lo, axis=1), acc
            )

        ws = jax.lax.fori_loop(
            j0, j1, gather, jnp.full((GROUP_ROWS, LANES), zero)
        )
        prod = edge_product(ws, streams.measure(off), op)

        @pl.when(jnp.any(prod != zero))
        def _scatter():
            d = streams.dst(off)

            def chunk(c, carry):
                sl = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
                out_ref[:, sl] = combine(
                    out_ref[:, sl], scatter_chunk(prod, d, c * LANES, op), op
                )
                return carry

            jax.lax.fori_loop(jnp.min(d) >> 7, (jnp.max(d) >> 7) + 1, chunk, 0)

        return carry

    jax.lax.fori_loop(0, ROWS_PER_BLOCK, row, 0)


def row_scratch():
    return [
        pltpu.VMEM((1, EDGE_BLOCK), jnp.int32),
        pltpu.VMEM((1, EDGE_BLOCK), jnp.int32),
        pltpu.VMEM((1, EDGE_BLOCK), jnp.int32),
    ]


def stream_specs(cfg: HopCfg, pick):
    """BlockSpecs for one hop's streams. ``pick(i, *prefetch)`` names the
    edge block of grid step ``i``; a dictionary is resident."""
    widths = [cfg.src_width, cfg.dst_width]
    if cfg.m_mode != "none":
        widths.append(cfg.m_width if cfg.m_mode != "dense" else 0)
    specs = [
        pl.BlockSpec(
            (None, 1, EDGE_BLOCK * w // 32 if w else EDGE_BLOCK),
            lambda g, i, *pf: (pick(i, *pf), 0, 0),
        )
        for w in widths
    ]
    if cfg.m_mode == "dict":
        specs.append(pl.BlockSpec(
            (1, padded(cfg.dict_len)), lambda g, i, *pf: (0, 0)
        ))
    return specs


def resident_spec(n: int):
    return pl.BlockSpec((GROUP_ROWS, padded(n)), lambda g, i, *pf: (g, 0))


def compiler_params(*resident_cols: int):
    """Scoped-VMEM request: the resident ``(8, n)`` groups, double-buffered,
    plus headroom for the streamed blocks and row scratch."""
    need = sum(2 * GROUP_ROWS * padded(n) * 4 for n in resident_cols) + 8 * 2**20
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=min(max(need, 32 * 2**20), VMEM_LIMIT_BYTES),
    )


def _hop_kernel(cfg: HopCfg, n_edges: int, op: str, *refs):
    na, bi, clist, ccnt, w_ref, *rest = refs
    n_in = cfg.n_operands()
    in_refs, out_ref, scratch = rest[:n_in], rest[n_in], rest[n_in + 1:]
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, IDENTITY[op], jnp.float32)

    @pl.when(i < na[0])
    def _compute():
        streams = BlockStreams(cfg, in_refs, scratch, n_edges, bi[i])
        hop_block(streams, w_ref, clist, ccnt, out_ref, op)


@functools.partial(
    jax.jit,
    static_argnames=("n_dst", "src_width", "dst_width", "m_mode", "m_width",
                     "op", "skip_chunks", "interpret", "label"),
)
def fragment_hop(
    weights: jnp.ndarray,  # f32[B, n_src]
    src: jnp.ndarray,  # i32[E] | uint32 words
    dst: jnp.ndarray,  # i32[E] | uint32 words
    measure: jnp.ndarray | None,  # per m_mode
    mdict: jnp.ndarray | None,
    block_idx: jnp.ndarray | None,  # i32[C] | None ⇒ every block
    n_active: jnp.ndarray | None,  # i32[1]
    *,
    n_dst: int,
    src_width: int = 0,
    dst_width: int = 0,
    m_mode: str = "none",
    m_width: int = 0,
    op: str = "sum",
    skip_chunks: bool = True,
    interpret: bool = False,
    label: str = "",
) -> jnp.ndarray:
    """The hop over ``E`` edges given in any order (``src``/``dst`` dense
    ids or BCA words). ``block_idx``/``n_active`` select edge blocks
    (kernels/active.py layout); ``None`` streams every block. Returns
    ``[B, n_dst]``. A ``label`` (the executor's hop) goes into the kernel's
    ``kernel_metadata`` as ``{"hop": label}``, where a device trace shows it
    beside the unchanged instruction name ``gqfast_hop``."""
    if op not in IDENTITY:
        raise ValueError(f"unknown combine op {op!r}")
    B, n_src = weights.shape
    E = n_edges(src, src_width, dst, dst_width)
    if E == 0:  # empty relation: no edge contributes, everything is ⊕-identity
        return jnp.full((B, n_dst), IDENTITY[op], jnp.float32)
    cfg = HopCfg(
        n_src, n_dst, src_width, dst_width, m_mode, m_width,
        0 if mdict is None else int(mdict.shape[0]),
    )
    operands, nb = edge_operands(cfg, E, src, dst, measure, mdict)
    if block_idx is None:
        block_idx = jnp.arange(nb, dtype=jnp.int32)
        n_active = jnp.asarray([nb], jnp.int32)
    wp = pad_frontier(jnp.asarray(weights, jnp.float32), n_src, op)
    clist, ccnt = chunk_lists(wp, op, skip_chunks)
    G = wp.shape[0] // GROUP_ROWS
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(G, block_idx.shape[0]),
        in_specs=[resident_spec(n_src)]
        + stream_specs(cfg, lambda i, na, bi, cl, cc: bi[i]),
        out_specs=resident_spec(n_dst),
        scratch_shapes=row_scratch(),
    )
    out = pl.pallas_call(
        functools.partial(_hop_kernel, cfg, E, op),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (wp.shape[0], padded(n_dst)), jnp.float32
        ),
        compiler_params=compiler_params(n_src, n_dst),
        interpret=interpret,
        name="gqfast_hop",
        metadata={"hop": label} if label else None,
    )(n_active, block_idx, clist, ccnt, wp, *operands)
    return out[:B, :n_dst]


def n_edges(src, src_width: int, dst, dst_width: int) -> int:
    """Edge count, read from a dense stream (a packed stream's word count
    rounds up, so one of src/dst must be dense)."""
    if not src_width:
        return int(src.shape[0])
    if not dst_width:
        return int(dst.shape[0])
    raise ValueError("a hop needs a dense src or a dense dst stream")
