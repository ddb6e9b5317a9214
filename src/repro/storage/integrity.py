"""Column integrity: CRC32C digests over the device column store.

The engine's entire value proposition rests on carefully encoded device
columns (§5 dense IDs, BCA/dictionary-packed words); a flipped bit in one
packed word silently poisons every query that streams it. This module gives
every device-resident column a verifiable identity:

  * :func:`crc32c` — CRC-32C (Castagnoli), the storage-industry checksum
    (iSCSI, ext4, Kudu/Parquet pages). Hardware-accelerated via
    ``google_crc32c`` when importable; otherwise a table-driven pure-Python
    fallback (identical values, slower — fine for test-sized columns).
  * :func:`column_digest` — per-column digest of both physical layers:
    ``encoded_crc`` over the stored device arrays exactly as HBM holds them
    (packed words / dense array / dictionary), and ``decoded_crc`` over the
    decoded view ``materialize()`` serves to the engine.
  * :func:`build_manifest` / :func:`attach_manifest` — the host-side
    manifest mapping ``I_<table>.<key>/<column>`` → digest, and its
    attachment to a live DB: once attached, ``materialize()`` verifies every
    concrete decode against ``decoded_crc`` (storage/columns.py) and the
    scrubber (robust/scrub.py) re-hashes encoded bytes against
    ``encoded_crc`` a few columns per tick.

Digest addresses are strings (JSON-manifest friendly): ``I_DT.doc/__dst__``
for the hop's destination column, ``I_DT.doc/<measure>`` for measures, and
``I_DT.doc/pull/<column>`` for the permuted copies of the index's pull
stream (``pull/__key__`` for its key per edge), which the hop kernel reads.
"""
from __future__ import annotations

from typing import Any, Iterable

import numpy as np

from .columns import DenseColumn, DeviceColumn, DictPackedColumn, PackedColumn

try:  # hardware CRC32C when the wheel is present
    import google_crc32c as _gcrc
except ImportError:  # pragma: no cover - environment-dependent
    _gcrc = None

#: CRC-32C (Castagnoli) reflected polynomial.
_POLY = 0x82F63B78

_TABLE: list[int] | None = None


def _table() -> list[int]:
    global _TABLE
    if _TABLE is None:
        t = []
        for i in range(256):
            c = i
            for _ in range(8):
                c = (c >> 1) ^ _POLY if c & 1 else c >> 1
            t.append(c)
        _TABLE = t
    return _TABLE


def _as_bytes(data: Any) -> bytes:
    if isinstance(data, (bytes, bytearray, memoryview)):
        return bytes(data)
    return np.ascontiguousarray(np.asarray(data)).tobytes()


def crc32c(data: Any, value: int = 0) -> int:
    """CRC-32C of ``data`` (bytes or array), continuing from ``value`` so
    multi-part digests (packed words + dictionary) chain one checksum."""
    buf = _as_bytes(data)
    if _gcrc is not None:
        return int(_gcrc.extend(value, buf))
    crc = value ^ 0xFFFFFFFF
    tab = _table()
    for b in buf:
        crc = tab[(crc ^ b) & 0xFF] ^ (crc >> 8)
    return crc ^ 0xFFFFFFFF


def crc32c_parts(parts: Iterable[Any]) -> int:
    """One chained CRC over an ordered sequence of buffers/arrays."""
    crc = 0
    for p in parts:
        crc = crc32c(p, crc)
    return crc


# ---------------------------------------------------------------------------
# Column digests
# ---------------------------------------------------------------------------


def encoded_parts(col: DeviceColumn) -> list[np.ndarray]:
    """The stored device arrays of ``col`` in digest order — exactly what HBM
    holds, no decode. The scrubber re-reads these."""
    if isinstance(col, DenseColumn):
        return [np.asarray(col.array)]
    if isinstance(col, DictPackedColumn):
        return [np.asarray(col.words), np.asarray(col.dictionary)]
    if isinstance(col, PackedColumn):
        return [np.asarray(col.words)]
    raise TypeError(f"not a device column: {type(col).__name__}")


def decode_fresh(col: DeviceColumn) -> np.ndarray:
    """The decoded view of ``col`` computed directly from the encoded arrays —
    byte-identical to ``materialize()`` output but bypassing the memo and the
    ``storage.materialize`` fault site, so it is usable as the trusted
    baseline while a corrupt-mode fault plan is live."""
    import jax.numpy as jnp

    from ..kernels import ops as K

    if isinstance(col, DenseColumn):
        return np.asarray(col.array)
    if isinstance(col, DictPackedColumn):
        return np.asarray(
            jnp.take(col.dictionary, K.bitunpack(col.words, col.width, col.count))
        )
    if isinstance(col, PackedColumn):
        return np.asarray(
            K.bitunpack(col.words, col.width, col.count).astype(col.out_dtype)
        )
    raise TypeError(f"not a device column: {type(col).__name__}")


def column_digest(col: DeviceColumn) -> dict[str, Any]:
    """Both-layer digest of one column: the encoded bytes as stored and the
    decoded view as served."""
    return {
        "kind": col.kind,
        "count": int(col.count),
        "encoded_crc": crc32c_parts(encoded_parts(col)),
        "decoded_crc": crc32c(decode_fresh(col)),
    }


def iter_columns(device_db) -> list[tuple[str, tuple[str, str], str, DeviceColumn]]:
    """Every device column as ``(addr, (table, key), column_name, col)``;
    ``addr`` is the manifest key ``I_<t>.<k>/<col>``. An index's pull
    stream, once built (``core.executor.attach_pull_streams``), adds its
    permuted copies as ``I_<t>.<k>/pull/<col>``, with ``pull/__key__`` for
    the index's key per edge."""
    out = []
    for (t, k), di in list(device_db.indexes.items()):
        cols = [("__dst__", di.dst_col), *di.measure_cols.items()]
        p = getattr(di, "pull", None)
        if p is not None:
            cols += [(f"pull/{name}", col) for name, col in p.columns()]
        for name, col in cols:
            out.append((f"I_{t}.{k}/{name}", (t, k), name, col))
    return out


def build_manifest(device_db) -> dict[str, dict[str, Any]]:
    """Digest every device column of a (trusted, freshly built or freshly
    verified) DB. This is the host-side source of truth the verified-read
    path and the scrubber check against."""
    return {addr: column_digest(col) for addr, _, _, col in iter_columns(device_db)}


def _install(col: DeviceColumn, tk: tuple[str, str], name: str,
             dig: dict[str, Any], verify_reads: bool) -> None:
    col._addr = (*tk, name)
    col._expected_crc = int(dig["decoded_crc"]) if verify_reads else None


def attach_manifest(device_db, manifest: dict[str, dict[str, Any]] | None = None,
                    verify_reads: bool = True) -> dict[str, dict[str, Any]]:
    """Install ``manifest`` (built fresh when None) on ``device_db`` and on
    each column. With ``verify_reads`` every subsequent concrete
    ``materialize()`` of a packed/dict/dense column checks its decoded bytes
    against the digest (storage/columns.py) — corruption is detected at the
    read that would otherwise poison a trace, healed from the memo when
    transient, raised as :class:`repro.robust.errors.IntegrityError` when
    persistent."""
    if manifest is None:
        manifest = build_manifest(device_db)
    device_db.integrity = manifest
    device_db.integrity_verify_reads = verify_reads
    for addr, tk, name, col in iter_columns(device_db):
        dig = manifest.get(addr)
        if dig is not None:
            _install(col, tk, name, dig, verify_reads)
    return manifest


def check_encoded(device_db, table: str, key: str, name: str,
                  col: DeviceColumn) -> None:
    """Raise :class:`repro.robust.errors.IntegrityError` unless ``col``'s
    stored bytes match the manifest's ``encoded_crc`` for
    ``I_<table>.<key>/<name>`` — the check on a CSR column before a
    pull-stream copy is permuted from it."""
    dig = device_db.integrity.get(f"I_{table}.{key}/{name}")
    actual = crc32c_parts(encoded_parts(col))
    if dig is not None and actual == int(dig["encoded_crc"]):
        return
    from ..robust.errors import IntegrityError

    raise IntegrityError(
        f"column I_{table}.{key}/{name} does not match its digest; no copy "
        "is derived from it",
        table=table, key=key, column=name, actual_crc=actual,
        expected_crc=None if dig is None else int(dig["encoded_crc"]),
    )


def cover_pull_stream(device_db, table: str, key: str, stream) -> None:
    """Add ``stream``, the pull stream of index ``(table, key)`` just
    permuted from its verified CSR columns, to the attached manifest and
    install its digests. A digest the manifest already holds for a copy (a
    snapshot's) must match the rebuilt bytes."""
    from ..robust.errors import IntegrityError

    manifest = device_db.integrity
    verify_reads = getattr(device_db, "integrity_verify_reads", True)
    for name, col in stream.columns():
        addr, name = f"I_{table}.{key}/pull/{name}", f"pull/{name}"
        dig = column_digest(col)
        old = manifest.get(addr)
        if old is not None and int(old["encoded_crc"]) != dig["encoded_crc"]:
            raise IntegrityError(
                f"rebuilt column {addr} does not match its recorded digest",
                table=table, key=key, column=name,
                expected_crc=int(old["encoded_crc"]),
                actual_crc=dig["encoded_crc"],
            )
        manifest[addr] = dig
        _install(col, (table, key), name, dig, verify_reads)


def detach_manifest(device_db) -> None:
    """Remove integrity state — columns return to zero-overhead reads."""
    if getattr(device_db, "integrity", None) is not None:
        device_db.integrity = None
    for _, _, _, col in iter_columns(device_db):
        col._expected_crc = None
        col._addr = None
        col._quarantined = False
