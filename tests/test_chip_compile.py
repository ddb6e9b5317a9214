"""Ahead-of-time compiles of the hop kernels for a described v5e chip.

The kernel tests elsewhere run in Pallas interpret mode on the CPU, which
never asks the TPU compiler (Mosaic) anything. These tests compile the
kernels the served path reaches, at the widths of the bring-up deployment
(1M documents, 27,883 terms, ~270k authors), for a ``v5e:2x2`` topology
that is described, not attached: dense and packed streams, full scans and
block lists, one query and a batch of eight, and one fused two-hop region;
and that a hop's label reaches the compiled kernel's ``kernel_metadata``
under the unchanged instruction names ``gqfast_hop`` / ``gqfast_fused_hops``.
A compile takes a second or two; it proves the kernel lowers and fits VMEM,
not that it is fast or right (the interpret-mode tests cover results).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test module.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.fragment_spmv import fragment_hop
from repro.kernels.fragment_spmv_fused import fragment_hops_fused
from repro.kernels.params import EDGE_BLOCK

N_DOCS, N_TERMS, N_AUTHORS = 1_000_000, 27_883, 270_122
E = 1 << 20  # edges: sets the grid length only, not the compiled body
C = E // EDGE_BLOCK


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but not
    # read back without the chip; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def sds(topo):
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)


def _compile(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


@pytest.mark.parametrize("B", [1, 8])
@pytest.mark.parametrize("op", ["sum", "min", "max", "bool"])
def test_dense_scan_term_to_doc(sds, op, B):
    """Term frontier → documents over the doc-sorted DT edges (full scan)."""
    _compile(
        fragment_hop,
        sds((B, N_TERMS), jnp.float32), sds((E,), jnp.int32),
        sds((E,), jnp.int32), sds((E,), jnp.float32), None, None, None,
        n_dst=N_DOCS, m_mode="dense", op=op,
    )


@pytest.mark.parametrize("B", [1, 8])
def test_packed_active_doc_to_term(sds, B):
    """Document frontier → terms: BCA-packed doc ids (20 bits) and a
    dictionary-packed measure, with a scalar-prefetched block list."""
    _compile(
        fragment_hop,
        sds((B, N_DOCS), jnp.float32), sds((E * 20 // 32,), jnp.uint32),
        sds((E,), jnp.int32), sds((E * 6 // 32,), jnp.uint32),
        sds((50,), jnp.float32), sds((C,), jnp.int32), sds((1,), jnp.int32),
        n_dst=N_TERMS, src_width=20, m_mode="dict", m_width=6, op="sum",
    )


@pytest.mark.parametrize("B", [1, 8])
def test_packed_dst_active_src_sorted(sds, B):
    """The source-sorted layout: packed 15-bit term ids as destinations."""
    _compile(
        fragment_hop,
        sds((B, N_DOCS), jnp.float32), sds((E,), jnp.int32),
        sds((E * 15 // 32,), jnp.uint32), sds((E * 6 // 32,), jnp.uint32),
        None, sds((C,), jnp.int32), sds((1,), jnp.int32),
        n_dst=N_TERMS, dst_width=15, m_mode="packed", m_width=6, op="sum",
    )


@pytest.mark.parametrize("B", [1, 8])
def test_fused_author_doc_term(sds, B):
    """A fused two-hop region whose intermediate is the 1M-document domain,
    resident in VMEM: authors → documents (semijoin binarize) → terms."""
    hop1 = (sds((E,), jnp.int32), sds((E,), jnp.int32), None, None)
    hop2 = (sds((E * 20 // 32,), jnp.uint32), sds((E,), jnp.int32),
            sds((E,), jnp.float32), None)
    _compile(
        fragment_hops_fused,
        sds((B, N_AUTHORS), jnp.float32), hop1, hop2,
        sds((N_DOCS,), jnp.float32),
        n_mid=N_DOCS, n_dst=N_TERMS, cfg1_modes=(0, 0, "none", 0),
        cfg2_modes=(20, 0, "dense", 0), op="sum", mid_binarize=True,
    )


def _kernel_lines(compiled, name: str) -> list[str]:
    """The compiled module's instructions named ``name`` (``%name`` or
    ``%name.N``), each with the text up to the next instruction (the
    kernel's metadata JSON spans lines)."""
    text = compiled.as_text()
    return [chunk for chunk in re.split(r"\n(?=\s*(?:ROOT\s+)?%)", text)
            if re.match(rf"\s*(?:ROOT\s+)?%{name}(\.\d+)? = ", chunk)]


def test_hop_label_reaches_kernel_metadata(sds):
    """The executor's hop label goes into the kernel's ``kernel_metadata``
    (what a device trace's event shows); the instruction keeps its name."""
    label = "h2:DT.Term->Document:pull"
    compiled = _compile(
        fragment_hop,
        sds((8, N_TERMS), jnp.float32), sds((E,), jnp.int32),
        sds((E,), jnp.int32), sds((E,), jnp.float32), None, None, None,
        n_dst=N_DOCS, m_mode="dense", op="sum", label=label,
    )
    lines = _kernel_lines(compiled, "gqfast_hop")
    assert len(lines) == 1
    assert "kernel_metadata" in lines[0] and f'"hop":"{label}"' in lines[0]


def test_fused_label_names_both_hops(sds):
    label = "h0:DA.Author->Document:pull+h1:DT.Doc->Term:fused"
    hop1 = (sds((E,), jnp.int32), sds((E,), jnp.int32), None, None)
    hop2 = (sds((E,), jnp.int32), sds((E,), jnp.int32), None, None)
    compiled = _compile(
        fragment_hops_fused,
        sds((8, N_AUTHORS), jnp.float32), hop1, hop2, None,
        n_mid=N_DOCS, n_dst=N_TERMS, cfg1_modes=(0, 0, "none", 0),
        cfg2_modes=(0, 0, "none", 0), op="sum", label=label,
    )
    lines = _kernel_lines(compiled, "gqfast_fused_hops")
    assert len(lines) == 1
    assert f'"hop":"{label}"' in lines[0]
    assert not _kernel_lines(compiled, "gqfast_hop")
