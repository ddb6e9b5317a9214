"""Device storage policy: per-column encoding choice + real device-byte report.

Extends the paper's §5 space model from the host byte-array encodings to the
*device* representations the kernels actually read. Candidate layouts per
column (sizes in device bytes, uint32-word granularity):

  dense   4·E                          (full-width int32/float32 CSR array)
  packed  4·⌈E·w/32⌉                   w = ⌈log2 D⌉        (BCA on device)
  dict    4·⌈E·w_u/32⌉ + 4·u           w_u = ⌈log2 u⌉, u = #distinct values
                                       (DictBCA/Huffman substitute)

The chooser picks the minimum — the Fig. 12 decision procedure evaluated on
the device layouts instead of the host byte streams. Keys (the hop's
``dst_ids``) never take ``dict``: the fused hop kernel decodes them straight
to entity ids, and FK domains are already dense so a dictionary is pure
overhead. Columns needing ≥ 32 bits stay dense (packing saves nothing), and
signed columns never bit-pack (the bit layouts are unsigned, codecs §5
contract) though ``dict`` still applies — the dictionary stores original
values.

``resolve_device_encoding`` layers the user-facing override surface
(`GQFastDatabase(device_encodings=...)`) on top: a global mode
(``"auto" | "dense" | "packed"``) or a per-column dict keyed by
``(table, key, column)`` with ``"auto"`` filling the gaps.
"""
from __future__ import annotations

import math
from typing import Any

import jax.numpy as jnp
import numpy as np

from .columns import DenseColumn, DeviceColumn, DictPackedColumn, PackedColumn

DEVICE_ENCODINGS = ("dense", "packed", "dict")

# the fused hop kernel keeps the whole dictionary VMEM-resident next to the
# frontier and accumulator vectors, so cap its size (64k fp32 slots = 256 KB —
# comfortable inside the ~16 MB/core budget); larger-cardinality columns fall
# back to packed/dense even when dict would win on HBM bytes
DICT_MAX_ENTRIES = 1 << 16


def _codec_utils():
    """Deferred import: ``repro.core.__init__`` imports the engine, which
    imports this package — a module-level ``from ..core...`` import would
    therefore cycle whenever ``repro.storage`` loads first."""
    from ..core.codecs import bits_needed
    from ..core.fragments import _pack_words

    return bits_needed, _pack_words


def column_uniques(values: np.ndarray):
    """Zero-arg memo of ``np.unique(values, return_counts=True)`` — the chooser
    and the dict builder share one O(E log E) scan instead of each running
    their own (and no scan happens at all unless someone asks)."""
    memo: list = []

    def get():
        if not memo:
            memo.append(np.unique(values, return_counts=True))
        return memo[0]

    return get


def _candidate_bytes(
    values: np.ndarray, domain: int, is_key: bool, uniques=None
) -> dict[str, int]:
    bits_needed, _ = _codec_utils()
    E = int(values.shape[0])
    w = bits_needed(domain)
    cand = {"dense": 4 * E}
    signed = bool(E) and int(values.min()) < 0
    if w < 32 and not signed:  # bit packing is unsigned (codecs contract)
        cand["packed"] = 4 * math.ceil(E * w / 32)
    if not is_key and E:
        # dict stores original values, so signed columns are fine here
        u = int((uniques or column_uniques(values))()[0].shape[0])
        wu = bits_needed(u)
        if wu < 32 and u <= DICT_MAX_ENTRIES:
            cand["dict"] = 4 * math.ceil(E * wu / 32) + 4 * u
    return cand


def choose_device_encoding(
    values: np.ndarray, domain: int, is_key: bool, uniques=None
) -> str:
    """§5-style chooser over the device layouts: minimum candidate bytes
    (ties go to the less exotic layout: dense < packed < dict)."""
    cand = _candidate_bytes(values, domain, is_key, uniques)
    return min(DEVICE_ENCODINGS, key=lambda e: (cand.get(e, math.inf), DEVICE_ENCODINGS.index(e)))


def resolve_device_encoding(
    spec: str | dict | None,
    addr: tuple[str, str, str],
    values: np.ndarray,
    domain: int,
    is_key: bool,
    uniques=None,
) -> str:
    """Resolve the user-facing ``device_encodings`` surface for one column.
    ``addr`` = (table, key, column) — the index-qualified column address."""
    if isinstance(spec, dict):
        enc = spec.get(addr, "auto")
    else:
        enc = spec or "auto"
    if enc == "auto":
        return choose_device_encoding(values, domain, is_key, uniques)
    if enc not in DEVICE_ENCODINGS:
        raise ValueError(f"unknown device encoding {enc!r} for {addr}")
    if enc == "dict" and is_key:
        raise ValueError(f"dict encoding is measure-only; {addr} is a key column")
    # requested packing that cannot apply (≥ 32-bit or signed values — bit
    # packing is unsigned) degrades to dense; one O(E) min-reduce, never the
    # chooser's O(E log E) unique scan
    bits_needed, _ = _codec_utils()
    if enc == "packed" and (
        bits_needed(domain) >= 32
        or (values.shape[0] and int(values.min()) < 0)
    ):
        return "dense"
    return enc


def build_device_column(cf, enc: str, out_dtype, uniques=None) -> DeviceColumn:
    """Materialize one :class:`~repro.core.fragments.ColumnFragments` on device
    under ``enc``. Reuses the loader's bit-packed words when it kept them."""
    bits_needed, _pack_words = _codec_utils()
    if enc == "dense":
        return DenseColumn(jnp.asarray(cf.values, dtype=out_dtype))
    if enc == "packed":
        width = cf.packed_width or bits_needed(cf.domain)
        words = cf.packed if cf.packed is not None else _pack_words(cf.values, width)
        return PackedColumn(jnp.asarray(words), width, int(cf.values.shape[0]), out_dtype)
    if enc == "dict":
        vals, counts = (uniques or column_uniques(cf.values))()
        width = bits_needed(len(vals))
        # degenerate (indices as wide as the data) or VMEM-hostile (dictionary
        # too large to sit resident in the fused kernel): stay dense
        if width >= 32 or len(vals) > DICT_MAX_ENTRIES:
            return DenseColumn(jnp.asarray(cf.values, dtype=out_dtype))
        order = np.argsort(-counts, kind="stable")
        dictionary = vals[order]
        # frequency rank per sorted-unique slot; O(E log u) via searchsorted,
        # never sized by the value *range* (values may be huge or negative)
        rank = np.empty(len(vals), dtype=np.int64)
        rank[order] = np.arange(len(vals))
        words = _pack_words(rank[np.searchsorted(vals, cf.values)], width)
        return DictPackedColumn(
            jnp.asarray(words), width, int(cf.values.shape[0]),
            jnp.asarray(dictionary, dtype=out_dtype),
        )
    raise ValueError(f"unknown device encoding {enc!r}")


def permute_column(col: DeviceColumn, perm: np.ndarray) -> DeviceColumn:
    """``col`` with its values in the order ``perm`` (host int array), in the
    same encoding and width: dense arrays are permuted, packed index words
    are unpacked on the host, permuted and re-packed, and a dictionary is
    shared with ``col``."""
    from ..core.fragments import _pack_words, _unpack_words

    if isinstance(col, DenseColumn):
        return DenseColumn(jnp.asarray(np.asarray(col.array)[perm]))
    if not isinstance(col, (PackedColumn, DictPackedColumn)):
        raise TypeError(f"not a device column: {type(col).__name__}")
    idx = _unpack_words(np.asarray(col.words), col.width, col.count)[perm]
    words = jnp.asarray(_pack_words(idx, col.width))
    if isinstance(col, DictPackedColumn):
        return DictPackedColumn(words, col.width, col.count, col.dictionary)
    return PackedColumn(words, col.width, col.count, col.out_dtype)


def device_space_report(device_db) -> dict[str, Any]:
    """Real device bytes, per index per column — what HBM actually holds, as
    opposed to the host byte-array accounting of ``FragmentIndex.total_bytes``.
    ``dense_bytes`` is the decoded-CSR baseline for the same data, so
    ``ratio`` directly states the §5-style compression factor on device.
    ``materialized_bytes`` counts decoded fallback copies currently pinned by
    the ``materialize()`` memo (fragment_loop / distributed prepares): those
    columns occupy packed *plus* dense bytes until the database is dropped, so
    the compression ratio only holds while ``materialized_bytes`` is 0.
    ``pull_bytes`` is an index's pull stream, once built (its permuted
    copies of the key ids and columns; a dictionary is shared), counted in
    its ``device_bytes``."""
    rep: dict[str, Any] = {
        "indexes": {}, "total_bytes": 0, "dense_bytes": 0, "materialized_bytes": 0,
    }

    def arr_bytes(a) -> int:
        return int(a.size) * a.dtype.itemsize if a is not None else 0

    for (t, k), di in device_db.indexes.items():
        cols = {}
        struct = arr_bytes(di.indptr) + arr_bytes(di.src_ids) + arr_bytes(di.degrees)
        total = struct
        dense_total = struct
        mat_total = 0
        for name, col in [("__dst__", di.dst_col), *di.measure_cols.items()]:
            b, db_ = col.device_nbytes, 4 * col.count
            cols[name] = {"kind": col.kind, "device_bytes": b, "dense_bytes": db_}
            if col.materialized_nbytes:
                cols[name]["materialized_bytes"] = col.materialized_nbytes
            total += b
            dense_total += db_
            mat_total += col.materialized_nbytes
        entry = {"columns": cols, "struct_bytes": struct}
        p = getattr(di, "pull", None)
        if p is not None:
            own = [c for _, c in p.columns()]
            entry["pull_bytes"] = sum(
                c.device_nbytes - arr_bytes(getattr(c, "dictionary", None))
                for c in own)
            total += entry["pull_bytes"]
            dense_total += sum(4 * c.count for c in own)
        entry.update(device_bytes=total, dense_bytes=dense_total)
        rep["indexes"][f"I_{t}.{k}"] = entry
        rep["total_bytes"] += total
        rep["dense_bytes"] += dense_total
        rep["materialized_bytes"] += mat_total
    rep["ratio"] = rep["dense_bytes"] / max(rep["total_bytes"], 1)
    return rep
