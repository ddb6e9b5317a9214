"""Checksummed, generation-stamped database snapshots (DESIGN.md §Durability).

The engine rebuilds its §5 data organization — fragment indexes plus the
compressed device column store — from raw tables on every process start.
This module makes that state durable and *verifiable*:

  * :func:`snapshot_db` persists a ``GQFastDatabase`` as ``gen_<n>/`` under a
    snapshot directory: one ``.npy`` file per logical array plus a
    ``MANIFEST.json`` carrying a CRC32C per array, the schema/layout
    metadata, and the per-column integrity digests
    (``storage/integrity.py``). Device columns are written as their
    *encoded* bytes (packed BCA words, dictionaries, dense arrays) so
    restore round-trips without re-encoding — the snapshot is the wire
    layout, not a logical dump. Publication is crash-safe via the shared
    atomic writer (``ckpt/atomic.py``): a generation is either fully visible
    with fsynced contents or absent.

  * :func:`restore_db` loads a generation, verifies **every** array file
    against its manifest CRC (and the rebuilt device columns against their
    encoded digests) *before* the database is handed to the engine, and
    raises a typed, non-retryable
    :class:`~repro.robust.errors.IntegrityError` naming the offending
    table/column on any mismatch — a corrupted snapshot never serves data.
    The restored DB carries its integrity manifest, so verified reads and
    the scrubber (robust/scrub.py) work out of the box.

Layout::

    <dir>/gen_0000000042/
        MANIFEST.json            # format, generation, schema, arrays, digests
        arrays/a00000.npy …      # one file per logical array (manifest maps
                                 # logical name → file + crc32c/dtype/shape)

Logical array names: ``host/<t>.<k>/indptr``, ``host/<t>.<k>/<col>/values``
(+``/packed``), ``dev/<t>.<k>/<col>/{array|words|dict}``,
``dev/<t>.<k>/block_src_{min,max}``, ``attr/<entity>/<name>``. Derivable
arrays (CSR ``src_ids``, ``degrees``) are rebuilt on restore rather than
stored, and each index's pull stream by the engine that serves the restored
database (``core.executor.attach_pull_streams``), against the digests the
snapshot recorded for it. Relationship-table rows are reconstructed from the
fk1-direction index, so restored raw tables are in (fk1, fk2)-sorted order —
relationally identical to the originals (aggregation is order-independent),
not byte-identical row order.

Fault site ``snapshot.load`` (robust/faults.py): ``raise``/``delay`` fire at
restore entry; ``corrupt`` transforms each loaded array *before* checksum
verification, so chaos plans can prove restore-time corruption is caught.
"""
from __future__ import annotations

import json
import math
import os
import time
from typing import Any

import numpy as np

from ..ckpt.atomic import list_stamped, publish_dir, retain_stamped, stamped_name
from ..robust import faults as _faults
from ..robust.errors import IntegrityError
from .columns import DenseColumn, DictPackedColumn, PackedColumn
from .integrity import (
    attach_manifest,
    build_manifest,
    crc32c,
    crc32c_parts,
    encoded_parts,
)

#: Manifest format version — bump on layout changes; restore refuses formats
#: it does not understand rather than misreading them.
FORMAT = 1

GEN_PREFIX = "gen_"
MANIFEST = "MANIFEST.json"
ARRAY_DIR = "arrays"


def list_generations(directory: str) -> list[int]:
    return list_stamped(directory, GEN_PREFIX)


def latest_generation(directory: str) -> int | None:
    gens = list_generations(directory)
    return gens[-1] if gens else None


def generation_path(directory: str, generation: int) -> str:
    return os.path.join(directory, stamped_name(GEN_PREFIX, generation))


# ---------------------------------------------------------------------------
# Snapshot (write)
# ---------------------------------------------------------------------------


def _device_column_arrays(col) -> dict[str, np.ndarray]:
    """The encoded device arrays of one column keyed by their role — written
    to disk exactly as stored, the no-re-encoding contract."""
    if isinstance(col, DenseColumn):
        return {"array": np.asarray(col.array)}
    if isinstance(col, DictPackedColumn):
        return {"words": np.asarray(col.words), "dict": np.asarray(col.dictionary)}
    if isinstance(col, PackedColumn):
        return {"words": np.asarray(col.words)}
    raise TypeError(f"not a device column: {type(col).__name__}")


def _collect(db) -> tuple[dict[str, np.ndarray], dict[str, Any]]:
    """Flatten ``db`` into (logical-name → host array, schema/layout meta)."""
    arrays: dict[str, np.ndarray] = {}
    indexes_meta: dict[str, Any] = {}
    for (t, k), idx in db.host_indexes.items():
        iid = f"{t}.{k}"
        arrays[f"host/{iid}/indptr"] = np.asarray(idx.indptr)
        cols_meta: dict[str, Any] = {}
        for c, cf in idx.columns.items():
            arrays[f"host/{iid}/{c}/values"] = np.asarray(cf.values)
            if cf.packed is not None:
                arrays[f"host/{iid}/{c}/packed"] = np.asarray(cf.packed)
            cols_meta[c] = {
                "domain": int(cf.domain),
                "encoding": cf.encoding,
                "encoded_bytes": int(cf.encoded_bytes),
                "packed_width": int(cf.packed_width),
                "has_packed": cf.packed is not None,
            }
        di = db.device.indexes[(t, k)]
        dev_meta: dict[str, Any] = {}
        for name, col in [("__dst__", di.dst_col), *di.measure_cols.items()]:
            for role, arr in _device_column_arrays(col).items():
                arrays[f"dev/{iid}/{name}/{role}"] = arr
            if isinstance(col, DenseColumn):
                odt = col.array.dtype
            elif isinstance(col, DictPackedColumn):
                odt = col.dictionary.dtype
            else:
                odt = col.out_dtype
            dev_meta[name] = {
                "kind": col.kind,
                "count": int(col.count),
                "width": int(getattr(col, "width", 0)),
                "out_dtype": np.dtype(odt).name,
            }
        if di.block_src_min is not None:
            arrays[f"dev/{iid}/block_src_min"] = np.asarray(di.block_src_min)
            arrays[f"dev/{iid}/block_src_max"] = np.asarray(di.block_src_max)
        indexes_meta[iid] = {
            "table": t, "key": k, "key_entity": idx.key_entity,
            "num_edges": int(idx.num_edges),
            "columns": cols_meta, "device": dev_meta,
        }
    for e in db.schema.entities.values():
        for a, col in e.attributes.items():
            arrays[f"attr/{e.name}/{a}"] = np.asarray(col)
    schema_meta = {
        "entities": {
            e.name: {"size": int(e.size), "attributes": sorted(e.attributes)}
            for e in db.schema.entities.values()
        },
        "relationships": {
            r.name: {
                "fk1": r.fk1, "fk2": r.fk2,
                "entity1": r.entity1, "entity2": r.entity2,
                "measures": list(r.measures),
            }
            for r in db.schema.relationships.values()
        },
    }
    return arrays, {"schema": schema_meta, "indexes": indexes_meta}


def snapshot_db(db, directory: str, keep: int | None = None) -> str:
    """Persist ``db`` as the next generation under ``directory`` and return
    the published path. ``keep`` ages out all but the newest ``keep``
    generations (None: keep everything). Atomic: a crash mid-write leaves no
    partially visible generation."""
    arrays, meta = _collect(db)
    generation = (latest_generation(directory) or 0) + 1
    manifest: dict[str, Any] = {
        "format": FORMAT,
        "generation": generation,
        "created": time.time(),
        **meta,
        "integrity": getattr(db.device, "integrity", None) or build_manifest(db.device),
        "arrays": {},
    }
    width = max(5, int(math.ceil(math.log10(max(len(arrays), 2)))))
    for i, name in enumerate(sorted(arrays)):
        arr = arrays[name]
        manifest["arrays"][name] = {
            "file": f"a{i:0{width}d}.npy",
            "crc32c": crc32c(arr),
            "dtype": str(arr.dtype),
            "shape": list(arr.shape),
            "nbytes": int(arr.nbytes),
        }

    def write(tmp: str) -> None:
        adir = os.path.join(tmp, ARRAY_DIR)
        os.makedirs(adir)
        for name, spec in manifest["arrays"].items():
            np.save(os.path.join(adir, spec["file"]), arrays[name])
        with open(os.path.join(tmp, MANIFEST), "w") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)

    final = publish_dir(generation_path(directory, generation), write,
                        tmp_prefix=".tmp_snap_")
    if keep is not None:
        retain_stamped(directory, GEN_PREFIX, keep)
    return final


# ---------------------------------------------------------------------------
# Restore (read + verify)
# ---------------------------------------------------------------------------


def _name_context(name: str) -> dict[str, Any]:
    """Best-effort (table, key, column) context parsed from a logical array
    name — what the IntegrityError carries so operators know *which* column
    went bad, not just which file."""
    parts = name.split("/")
    ctx: dict[str, Any] = {"array": name}
    if len(parts) >= 2 and parts[0] in ("host", "dev") and "." in parts[1]:
        t, k = parts[1].split(".", 1)
        ctx["table"], ctx["key"] = t, k
        if len(parts) >= 3:
            ctx["column"] = parts[2]
    elif len(parts) == 3 and parts[0] == "attr":
        ctx["table"], ctx["column"] = parts[1], parts[2]
    return ctx


def read_manifest(gen_path: str) -> dict[str, Any]:
    mpath = os.path.join(gen_path, MANIFEST)
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except FileNotFoundError:
        raise
    except Exception as e:  # noqa: BLE001 — truncated/garbled JSON
        raise IntegrityError(
            f"snapshot manifest unreadable: {e}", path=mpath,
        ) from e
    if manifest.get("format") != FORMAT:
        raise IntegrityError(
            f"snapshot format {manifest.get('format')!r} not supported "
            f"(expected {FORMAT})", path=mpath, format=manifest.get("format"),
        )
    return manifest


def _load_array(gen_path: str, name: str, spec: dict[str, Any],
                generation: int, fault_site: str | None) -> np.ndarray:
    """Load + verify one array file. Any deviation — unreadable file, wrong
    dtype/shape (a flipped header byte), data bytes off-digest (a flipped
    payload byte) — raises IntegrityError; corrupted snapshots never return
    data."""
    path = os.path.join(gen_path, ARRAY_DIR, spec["file"])
    try:
        arr = np.load(path)
    except Exception as e:  # noqa: BLE001 — np.load raises a zoo of types
        raise IntegrityError(
            f"snapshot array {name!r} unreadable: {e}",
            path=path, generation=generation, **_name_context(name),
        ) from e
    if fault_site is not None:
        arr = _faults.corrupt(fault_site, arr)
    if str(arr.dtype) != spec["dtype"] or list(arr.shape) != spec["shape"]:
        raise IntegrityError(
            f"snapshot array {name!r} header mismatch: "
            f"{arr.dtype}{list(arr.shape)} != {spec['dtype']}{spec['shape']}",
            path=path, generation=generation, **_name_context(name),
        )
    actual = crc32c(arr)
    if actual != spec["crc32c"]:
        raise IntegrityError(
            f"snapshot array {name!r} failed checksum verification",
            path=path, generation=generation,
            expected_crc=spec["crc32c"], actual_crc=actual,
            **_name_context(name),
        )
    return arr


def _build_device_index(iid: str, imeta: dict[str, Any],
                        arrays: dict[str, np.ndarray], indptr: np.ndarray):
    """Rebuild one DeviceIndex straight from snapshot bytes — ``jnp.asarray``
    of the stored encodings, never the encoders."""
    import jax.numpy as jnp

    from ..core.executor import DeviceIndex
    from ..kernels import active as active_meta  # noqa: F401 (block ranges)

    src = np.repeat(
        np.arange(indptr.shape[0] - 1, dtype=np.int64), np.diff(indptr)
    )
    bmin = arrays.get(f"dev/{iid}/block_src_min")
    bmax = arrays.get(f"dev/{iid}/block_src_max")
    if bmin is None or bmax is None:
        bmin, bmax = active_meta.block_ranges(src)

    def col_for(name: str, cmeta: dict[str, Any]):
        base = f"dev/{iid}/{name}"
        out_dtype = np.dtype(cmeta["out_dtype"])
        if cmeta["kind"] == "dense":
            return DenseColumn(jnp.asarray(arrays[base + "/array"]))
        if cmeta["kind"] == "dict":
            return DictPackedColumn(
                jnp.asarray(arrays[base + "/words"]), int(cmeta["width"]),
                int(cmeta["count"]),
                jnp.asarray(arrays[base + "/dict"], dtype=out_dtype),
            )
        if cmeta["kind"] == "packed":
            return PackedColumn(
                jnp.asarray(arrays[base + "/words"]), int(cmeta["width"]),
                int(cmeta["count"]), out_dtype,
            )
        raise IntegrityError(
            f"snapshot device column {base!r} has unknown kind "
            f"{cmeta['kind']!r}", array=base, kind=cmeta["kind"],
        )

    dev_meta = imeta["device"]
    return DeviceIndex(
        indptr=jnp.asarray(indptr, dtype=jnp.int32),
        src_ids=jnp.asarray(src, dtype=jnp.int32),
        dst_col=col_for("__dst__", dev_meta["__dst__"]),
        degrees=jnp.asarray(np.diff(indptr), dtype=jnp.int32),
        measure_cols={
            name: col_for(name, cmeta)
            for name, cmeta in dev_meta.items() if name != "__dst__"
        },
        block_src_min=np.asarray(bmin, dtype=np.int32),
        block_src_max=np.asarray(bmax, dtype=np.int32),
    )


def restore_db(directory: str, generation: int | None = None,
               verify_reads: bool = True):
    """Rebuild a ``GQFastDatabase`` from snapshot generation ``generation``
    (default: latest). Every array file is checksum-verified and the rebuilt
    device columns are cross-checked against their encoded digests *before*
    the database object exists — on any mismatch this raises
    :class:`IntegrityError` and returns nothing. The integrity manifest is
    attached to the restored DB (``verify_reads`` additionally enables
    per-materialize decoded-view verification)."""
    import jax.numpy as jnp

    from ..core.engine import GQFastDatabase
    from ..core.executor import DeviceDB
    from ..core.fragments import ColumnFragments, FragmentIndex
    from ..core.schema import EntityTable, RelationshipTable, Schema

    _faults.fire("snapshot.load", directory=directory)
    if generation is None:
        generation = latest_generation(directory)
        if generation is None:
            raise FileNotFoundError(f"no snapshot generations in {directory}")
    gen_path = generation_path(directory, generation)
    manifest = read_manifest(gen_path)

    arrays = {
        name: _load_array(gen_path, name, spec, generation,
                          fault_site="snapshot.load")
        for name, spec in manifest["arrays"].items()
    }

    # --- schema -----------------------------------------------------------
    entities = {
        name: EntityTable(
            name, emeta["size"],
            {a: arrays[f"attr/{name}/{a}"] for a in emeta["attributes"]},
        )
        for name, emeta in manifest["schema"]["entities"].items()
    }
    relationships = {}
    for name, rmeta in manifest["schema"]["relationships"].items():
        iid = f"{name}.{rmeta['fk1']}"
        indptr = arrays[f"host/{iid}/indptr"]
        fk1_col = np.repeat(
            np.arange(indptr.shape[0] - 1, dtype=np.int64), np.diff(indptr)
        )
        cols = {rmeta["fk1"]: fk1_col,
                rmeta["fk2"]: arrays[f"host/{iid}/{rmeta['fk2']}/values"]}
        for m in rmeta["measures"]:
            cols[m] = arrays[f"host/{iid}/{m}/values"]
        relationships[name] = RelationshipTable(
            name, rmeta["fk1"], rmeta["fk2"],
            rmeta["entity1"], rmeta["entity2"], cols,
        )
    schema = Schema(entities, relationships)

    # --- host indexes + device store --------------------------------------
    host_indexes: dict[tuple[str, str], FragmentIndex] = {}
    dev: dict[tuple[str, str], Any] = {}
    for iid, imeta in manifest["indexes"].items():
        t, k = imeta["table"], imeta["key"]
        indptr = arrays[f"host/{iid}/indptr"]
        idx = FragmentIndex(t, k, imeta["key_entity"], indptr)
        for c, cmeta in imeta["columns"].items():
            idx.columns[c] = ColumnFragments(
                c, arrays[f"host/{iid}/{c}/values"], cmeta["domain"],
                cmeta["encoding"], cmeta["encoded_bytes"],
                packed=arrays.get(f"host/{iid}/{c}/packed"),
                packed_width=cmeta["packed_width"],
            )
        host_indexes[(t, k)] = idx
        dev[(t, k)] = _build_device_index(iid, imeta, arrays, indptr)

    attrs = {
        (e.name, a): jnp.asarray(col, dtype=jnp.float32)
        for e in schema.entities.values()
        for a, col in e.attributes.items()
    }
    device = DeviceDB(schema, dev, attrs, host_indexes)

    # final gate: the rebuilt device columns must hash to the digests the
    # snapshot recorded — catches writer/restorer layout drift, not just disk
    # corruption (file-level CRCs already verified above)
    digests = manifest.get("integrity", {})
    for (t, k), di in dev.items():
        for name, col in [("__dst__", di.dst_col), *di.measure_cols.items()]:
            dig = digests.get(f"I_{t}.{k}/{name}")
            if dig is None:
                continue
            actual = crc32c_parts(encoded_parts(col))
            if actual != dig["encoded_crc"]:
                raise IntegrityError(
                    f"restored column I_{t}.{k}/{name} does not match its "
                    "snapshot digest",
                    table=t, key=k, column=name, generation=generation,
                    expected_crc=dig["encoded_crc"], actual_crc=actual,
                )

    db = GQFastDatabase.from_parts(schema, host_indexes, device)
    attach_manifest(device, digests or None, verify_reads=verify_reads)
    return db


def load_column_arrays(directory: str, generation: int, table: str, key: str,
                       column: str) -> tuple[dict[str, np.ndarray], dict[str, Any]]:
    """Read (and checksum-verify) the encoded arrays of ONE device column
    from a snapshot — the scrubber's repair source. Returns (role → array,
    column meta). No fault site: heal reads must not be re-corrupted by the
    ``snapshot.load`` chaos spec aimed at full restores."""
    gen_path = generation_path(directory, generation)
    manifest = read_manifest(gen_path)
    iid = f"{table}.{key}"
    cmeta = manifest["indexes"][iid]["device"][column]
    base = f"dev/{iid}/{column}/"
    out = {
        name[len(base):]: _load_array(gen_path, name, spec, generation,
                                      fault_site=None)
        for name, spec in manifest["arrays"].items()
        if name.startswith(base)
    }
    if not out:
        raise IntegrityError(
            f"snapshot has no arrays for column I_{iid}/{column}",
            table=table, key=key, column=column, generation=generation,
        )
    return out, cmeta
