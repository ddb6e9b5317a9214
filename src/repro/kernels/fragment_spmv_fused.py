"""Pallas TPU kernel: pipelined 2-hop fused fragment join-aggregate.

GQ-Fast's bottom-up execution is *fully pipelined* — intermediate results are
never materialized. This kernel executes a whole
:class:`repro.core.lower.FusedHopOp` region in ONE grid pass: the first hop's
output frontier accumulates in a VMEM scratch group ``u`` (``(8, n_mid)``,
the same layout as a frontier group of :mod:`.fragment_spmv`), the region's
constant filter mask (and the second hop's semijoin binarize) is applied to
``u`` at the phase boundary, and the second hop streams its edge blocks
against the VMEM-resident ``u`` — the intermediate never round-trips through
HBM, and the two hops cost one kernel launch instead of two.

Grid layout: ``(batch groups, C1 + 1 + C2)``, where ``C1``/``C2`` are the
two hops' edge-block counts. Steps ``< C1`` run hop1 into ``u``; the step
``C1`` applies the mid mask/binarize and compacts ``u``'s active 128-lane
chunks into an SMEM list (hop2's gather reads only those, as the unfused
kernel does from its prefetched list); steps ``> C1`` run hop2 into the
output. Both phases run the per-block body of the unfused kernel
(:func:`.fragment_spmv.hop_block`), so results are bit-identical to the
unfused two-kernel composition on every semiring × encoding × skip mode. The
degenerate 1-hop+filter region runs a ``C1 + 1`` grid whose last step writes
the masked ``u`` to the output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fragment_spmv import (
    GROUP_ROWS,
    IDENTITY,
    LANES,
    BlockStreams,
    HopCfg,
    chunk_lists,
    compiler_params,
    edge_operands,
    hop_block,
    n_chunks,
    n_edges,
    pad_frontier,
    padded,
    resident_spec,
    row_scratch,
    stream_specs,
)


def binarize(w, op: str):
    """Semijoin ⋉ on a raw frontier vector — mirrors ``Semiring.binarize``
    exactly (bit-identity with the unfused path depends on it)."""
    if op == "sum":
        return (w > 0).astype(jnp.float32)
    zero = IDENTITY[op]
    return jnp.where(w != zero, jnp.float32(1.0), jnp.float32(zero))


def apply_mask(w, keep, op: str):
    """Predicate filter — mirrors ``Semiring.mask``: keep where >0, else 0̄."""
    return jnp.where(keep > 0, w, IDENTITY[op])


def _compact_chunks(u_ref, clist, ccnt, n_mid: int, op: str) -> None:
    """SMEM chunk list + prefix counts of ``u``'s non-identity chunks — the
    in-kernel twin of :func:`.fragment_spmv.chunk_lists`."""
    zero = IDENTITY[op]
    nc = n_chunks(n_mid)

    def body(c, count):
        x = u_ref[:, pl.ds(pl.multiple_of(c * LANES, LANES), LANES)]
        ccnt[c] = count
        clist[count] = c
        return count + jnp.any(x != zero).astype(jnp.int32)

    ccnt[nc] = jax.lax.fori_loop(0, nc, body, jnp.int32(0))


def _fused_kernel(C1, cfg1, cfg2, E1, E2, op, has_mask, mid_binarize, *refs):
    two_hop = cfg2 is not None
    cl1, cc1, w_ref, *rest = refs
    n1 = cfg1.n_operands()
    n2 = cfg2.n_operands() if two_hop else 0
    h1, h2 = rest[:n1], rest[n1:n1 + n2]
    k = n1 + n2
    mask_ref = rest[k] if has_mask else None
    k += has_mask
    out_ref, u_ref = rest[k], rest[k + 1]
    rows = rest[k + 2:k + 5]
    cl2, cc2 = rest[k + 5:k + 7] if two_hop else (None, None)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        u_ref[...] = jnp.full(u_ref.shape, IDENTITY[op], jnp.float32)
        out_ref[...] = jnp.full(out_ref.shape, IDENTITY[op], jnp.float32)

    @pl.when(i < C1)
    def _hop1():
        streams = BlockStreams(cfg1, h1, rows, E1, i)
        hop_block(streams, w_ref, cl1, cc1, u_ref, op)

    @pl.when(i == C1)
    def _mid():
        u = u_ref[...]
        if has_mask:
            u = apply_mask(u, mask_ref[...], op)
        if not two_hop:
            out_ref[...] = u
            return
        if mid_binarize:
            u = binarize(u, op)
        u_ref[...] = u
        _compact_chunks(u_ref, cl2, cc2, cfg1.n_dst, op)

    if two_hop:
        @pl.when(i > C1)
        def _hop2():
            streams = BlockStreams(cfg2, h2, rows, E2, i - C1 - 1)
            hop_block(streams, u_ref, cl2, cc2, out_ref, op)


def _fused_call(
    weights, hop1, hop2, mid_mask, *,
    n_mid, n_dst, cfg1_modes, cfg2_modes, op, mid_binarize, skip_chunks,
    interpret, label,
):
    if op not in IDENTITY:
        raise ValueError(f"unknown combine op {op!r}")
    B, n_src = weights.shape
    two_hop = hop2 is not None
    has_mask = mid_mask is not None

    def cfg_of(hop, modes, n_in, n_out):
        sw, dw, mm, mw = modes
        return HopCfg(n_in, n_out, sw, dw, mm, mw,
                      0 if hop[3] is None else int(hop[3].shape[0]))

    cfg1 = cfg_of(hop1, cfg1_modes, n_src, n_mid)
    E1 = n_edges(hop1[0], cfg1.src_width, hop1[1], cfg1.dst_width)
    ops1, C1 = edge_operands(cfg1, E1, *hop1)
    wp = pad_frontier(jnp.asarray(weights, jnp.float32), n_src, op)
    cl1, cc1 = chunk_lists(wp, op, skip_chunks)
    G = wp.shape[0] // GROUP_ROWS
    out_n = n_dst if two_hop else n_mid
    mask_ops, mask_specs = [], []
    if has_mask:
        mm = jnp.asarray(mid_mask, jnp.float32).reshape(1, -1)
        mask_ops = [jnp.pad(mm, ((0, 0), (0, padded(n_mid) - n_mid)))]
        mask_specs = [pl.BlockSpec((1, padded(n_mid)), lambda g, i, *pf: (0, 0))]
    scratch = [pltpu.VMEM((GROUP_ROWS, padded(n_mid)), jnp.float32)] + row_scratch()
    if two_hop:
        cfg2 = cfg_of(hop2, cfg2_modes, n_mid, n_dst)
        E2 = n_edges(hop2[0], cfg2.src_width, hop2[1], cfg2.dst_width)
        ops2, C2 = edge_operands(cfg2, E2, *hop2)
        in_specs = (
            [resident_spec(n_src)]
            + stream_specs(cfg1, lambda i, *_: jnp.minimum(i, C1 - 1))
            + stream_specs(cfg2, lambda i, *_: jnp.clip(i - C1 - 1, 0, C2 - 1))
            + mask_specs
        )
        operands = [wp, *ops1, *ops2, *mask_ops]
        scratch += [pltpu.SMEM((n_chunks(n_mid),), jnp.int32),
                    pltpu.SMEM((n_chunks(n_mid) + 1,), jnp.int32)]
        steps = C1 + 1 + C2
    else:
        cfg2, E2 = None, 0
        in_specs = (
            [resident_spec(n_src)]
            + stream_specs(cfg1, lambda i, *_: jnp.minimum(i, C1 - 1))
            + mask_specs
        )
        operands = [wp, *ops1, *mask_ops]
        steps = C1 + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(G, steps),
        in_specs=in_specs,
        out_specs=resident_spec(out_n),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _fused_kernel, C1, cfg1, cfg2, E1, E2, op, has_mask, mid_binarize,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((wp.shape[0], padded(out_n)), jnp.float32),
        compiler_params=compiler_params(n_src, n_mid, out_n),
        interpret=interpret,
        name="gqfast_fused_hops",
        metadata={"hop": label} if label else None,
    )(cl1, cc1, *operands)
    return out[:B, :out_n]


@functools.partial(
    jax.jit,
    static_argnames=("n_mid", "n_dst", "cfg1_modes", "cfg2_modes", "op",
                     "mid_binarize", "skip_chunks", "interpret", "label"),
)
def fragment_hops_fused(
    weights: jnp.ndarray,  # f32[B, n_src] — hop1's incoming frontier
    hop1: tuple,  # (src, dst, measure, mdict) streams per cfg1_modes
    hop2: tuple | None,  # hop2 streams; None ⇒ degenerate 1-hop region
    mid_mask,  # f32[n_mid] ∧ of member filter masks | None
    *,
    n_mid: int,
    n_dst: int,
    cfg1_modes: tuple,  # (src_width, dst_width, m_mode, m_width)
    cfg2_modes: tuple | None = None,
    op: str = "sum",
    mid_binarize: bool = False,
    skip_chunks: bool = True,
    interpret: bool = False,
    label: str = "",
) -> jnp.ndarray:
    """Fused region over a ``[B, n_src]`` frontier → ``[B, n_dst]``. A
    ``label`` (both member hops, ``+``-joined) goes into the kernel's
    ``kernel_metadata`` as ``{"hop": label}``."""
    return _fused_call(
        weights, hop1, hop2, mid_mask,
        n_mid=n_mid, n_dst=n_dst, cfg1_modes=cfg1_modes, cfg2_modes=cfg2_modes,
        op=op, mid_binarize=mid_binarize and hop2 is not None,
        skip_chunks=skip_chunks, interpret=interpret, label=label,
    )
