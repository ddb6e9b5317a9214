"""Durability layer: checksummed snapshots, integrity scrubbing, hot swap.

Covers DESIGN.md §Durability end to end:

  * snapshot round trips — every device encoding × both strategies produce
    bit-identical query results after restore, without re-encoding;
  * detection — ANY single flipped byte in ANY snapshot array file makes
    restore raise IntegrityError (naming the offending table/column), never
    return data;
  * verified reads — a corrupted materialize is healed from the memo when
    transient, raised as IntegrityError when persistent;
  * scrubbing — at-rest corruption is detected, quarantined, healed from
    snapshot, and queries are bit-identical afterwards;
  * hot swap — load_generation warms a new generation; a corrupted
    generation rolls back without touching serving state;
  * the shared atomic writer and the thread-safety hardening under it all.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.engine import GQFastDatabase, GQFastEngine
from repro.data import synth_graph as SG
from repro.obs.metrics import MetricsRegistry
from repro.robust import IntegrityError, QueryError, Scrubber, faults
from repro.robust.faults import FaultPlan, FaultSpec
from repro.storage import (
    attach_manifest,
    build_manifest,
    crc32c,
    detach_manifest,
    latest_generation,
    list_generations,
    restore_db,
    snapshot_db,
)
from repro.storage.snapshot import load_column_arrays

SQL = ("SELECT d2.Term, COUNT(*) FROM DT d1 JOIN DT d2 ON d1.Doc = d2.Doc "
       "WHERE d1.Term = :t GROUP BY d2.Term")
SQL_SUM = ("SELECT dt.Doc, SUM(dt.Fre) FROM DT dt WHERE dt.Term = :t "
           "GROUP BY dt.Doc")


@pytest.fixture(scope="module")
def schema():
    return SG.make_pubmed(n_docs=250, n_terms=40, n_authors=80, seed=11)


def _db(schema, enc):
    return GQFastDatabase(schema, device_encodings=enc, account_space=False)


# ---------------------------------------------------------------------------
# CRC32C
# ---------------------------------------------------------------------------


def test_crc32c_vector():
    # the RFC 3720 check value every CRC-32C implementation must produce
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"") == 0


def test_crc32c_chaining():
    whole = crc32c(b"hello world")
    assert crc32c(b" world", crc32c(b"hello")) == whole


def test_crc32c_pure_python_fallback_matches():
    from repro.storage import integrity as I

    data = np.random.default_rng(0).integers(0, 2**32, 4096, np.uint32)
    got = I.crc32c(data)
    # force the table fallback and compare
    gcrc, I._gcrc = I._gcrc, None
    try:
        assert I.crc32c(data) == got
    finally:
        I._gcrc = gcrc


# ---------------------------------------------------------------------------
# Snapshot round trips
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("enc", ["dense", "packed", "auto"])
@pytest.mark.parametrize("strategy", ["frontier", "fragment_loop"])
def test_roundtrip_bit_identical(schema, enc, strategy, tmp_path):
    db = _db(schema, enc)
    eng = GQFastEngine(db, strategy=strategy)
    refs = [np.asarray(eng.prepare(sql)(t=7)) for sql in (SQL, SQL_SUM)]

    snapshot_db(db, str(tmp_path))
    db2 = restore_db(str(tmp_path))
    eng2 = GQFastEngine(db2, strategy=strategy)
    for sql, ref in zip((SQL, SQL_SUM), refs):
        got = np.asarray(eng2.prepare(sql)(t=7))
        assert got.dtype == ref.dtype
        assert np.array_equal(got, ref), f"{enc}/{strategy}: not bit-identical"


@pytest.mark.parametrize("enc", ["dense", "packed", "auto"])
def test_roundtrip_preserves_encodings(schema, enc, tmp_path):
    """Restore rebuilds columns from stored encoded bytes — same kinds, same
    words, no re-encode."""
    db = _db(schema, enc)
    snapshot_db(db, str(tmp_path))
    db2 = restore_db(str(tmp_path))
    for (t, k), di in db.device.indexes.items():
        di2 = db2.device.indexes[(t, k)]
        cols = [("__dst__", di.dst_col, di2.dst_col)] + [
            (m, c, di2.measure_cols[m]) for m, c in di.measure_cols.items()
        ]
        for name, a, b in cols:
            assert a.kind == b.kind, (t, k, name)
            if a.kind in ("packed", "dict"):
                assert np.array_equal(np.asarray(a.words), np.asarray(b.words))
                assert a.width == b.width and a.count == b.count
            if a.kind == "dict":
                assert np.array_equal(
                    np.asarray(a.dictionary), np.asarray(b.dictionary)
                )


def test_roundtrip_host_indexes_and_schema(schema, tmp_path):
    db = _db(schema, "auto")
    snapshot_db(db, str(tmp_path))
    db2 = restore_db(str(tmp_path))
    assert set(db2.host_indexes) == set(db.host_indexes)
    for key, idx in db.host_indexes.items():
        idx2 = db2.host_indexes[key]
        assert np.array_equal(idx.indptr, idx2.indptr)
        assert set(idx.columns) == set(idx2.columns)
        for c, cf in idx.columns.items():
            cf2 = idx2.columns[c]
            assert np.array_equal(cf.values, cf2.values)
            assert cf.encoding == cf2.encoding
            assert cf.encoded_bytes == cf2.encoded_bytes
    for e in schema.entities.values():
        e2 = db2.schema.entities[e.name]
        assert e2.size == e.size
        for a, col in e.attributes.items():
            assert np.array_equal(col, e2.attributes[a])
    db2.schema.validate()


def test_restored_db_has_manifest_and_verified_reads(schema, tmp_path):
    db = _db(schema, "packed")
    snapshot_db(db, str(tmp_path))
    db2 = restore_db(str(tmp_path))
    assert db2.device.integrity  # manifest attached…
    col = db2.device.indexes[("DT", "Doc")].dst_col
    assert col._expected_crc is not None  # …and reads are verified


def test_generations_and_retention(schema, tmp_path):
    db = _db(schema, "dense")
    for _ in range(3):
        snapshot_db(db, str(tmp_path))
    assert list_generations(str(tmp_path)) == [1, 2, 3]
    snapshot_db(db, str(tmp_path), keep=2)
    assert list_generations(str(tmp_path)) == [3, 4]
    assert latest_generation(str(tmp_path)) == 4
    # restore a specific, non-latest generation
    db2 = restore_db(str(tmp_path), generation=3)
    assert np.array_equal(
        np.asarray(db.device.indexes[("DT", "Doc")].indptr),
        np.asarray(db2.device.indexes[("DT", "Doc")].indptr),
    )


def test_restore_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore_db(str(tmp_path))


# ---------------------------------------------------------------------------
# Corruption detection: every flipped byte raises IntegrityError
# ---------------------------------------------------------------------------


def test_every_single_byte_flip_detected(schema, tmp_path):
    """Flip one byte in the middle of EVERY array file in turn: restore must
    raise IntegrityError each time, with the offending table/column named —
    a corrupted snapshot never yields a database object."""
    db = _db(schema, "auto")
    gen_path = snapshot_db(db, str(tmp_path))
    files = sorted(glob.glob(os.path.join(gen_path, "arrays", "*.npy")))
    assert len(files) > 10
    manifest = json.load(open(os.path.join(gen_path, "MANIFEST.json")))
    by_file = {spec["file"]: name for name, spec in manifest["arrays"].items()}
    for f in files:
        shutil.copy(f, f + ".bak")
        raw = bytearray(open(f, "rb").read())
        raw[len(raw) // 2] ^= 0x20
        open(f, "wb").write(bytes(raw))
        try:
            with pytest.raises(IntegrityError) as ei:
                restore_db(str(tmp_path))
            err = ei.value
            assert err.code == "INTEGRITY"
            assert not err.retryable
            logical = by_file[os.path.basename(f)]
            assert err.context.get("array") == logical
            # dev/host arrays must name their table; attrs their entity
            assert err.context.get("table"), logical
        finally:
            shutil.move(f + ".bak", f)
    restore_db(str(tmp_path))  # intact again → restores clean


def test_header_flip_detected(schema, tmp_path):
    """A flip in the .npy header (dtype/shape region, before the data bytes)
    must also surface as IntegrityError, not a numpy crash."""
    db = _db(schema, "dense")
    gen_path = snapshot_db(db, str(tmp_path))
    f = sorted(glob.glob(os.path.join(gen_path, "arrays", "*.npy")))[0]
    raw = bytearray(open(f, "rb").read())
    raw[9] ^= 0xFF  # inside the header dict
    open(f, "wb").write(bytes(raw))
    with pytest.raises(IntegrityError):
        restore_db(str(tmp_path))


def test_truncated_manifest_detected(schema, tmp_path):
    db = _db(schema, "dense")
    gen_path = snapshot_db(db, str(tmp_path))
    mpath = os.path.join(gen_path, "MANIFEST.json")
    open(mpath, "w").write(open(mpath).read()[:100])
    with pytest.raises(IntegrityError):
        restore_db(str(tmp_path))


def test_snapshot_load_fault_sites(schema, tmp_path):
    db = _db(schema, "dense")
    snapshot_db(db, str(tmp_path))
    # raise-mode: typed injected fault at restore entry
    plan = FaultPlan(seed=0, specs=[FaultSpec("snapshot.load", mode="raise",
                                              max_fires=1)])
    with faults.active(plan):
        with pytest.raises(QueryError):
            restore_db(str(tmp_path))
        restore_db(str(tmp_path))  # fires exhausted → succeeds
    # corrupt-mode: the loaded bytes are transformed pre-verification → caught
    plan = FaultPlan(seed=0, specs=[FaultSpec("snapshot.load", mode="corrupt",
                                              max_fires=1)])
    with faults.active(plan):
        with pytest.raises(IntegrityError):
            restore_db(str(tmp_path))


# ---------------------------------------------------------------------------
# Verified reads
# ---------------------------------------------------------------------------


def test_verified_read_transient_heals(schema):
    db = _db(schema, "packed")
    reg_before = None
    attach_manifest(db.device)
    col = db.device.indexes[("DT", "Doc")].dst_col
    truth = np.asarray(col.materialize())
    from repro.obs.metrics import REGISTRY

    heals0 = REGISTRY.counter("robust.integrity.read_heals").value
    plan = FaultPlan(seed=0, specs=[FaultSpec(
        "storage.materialize", mode="corrupt", max_fires=1)])
    with faults.active(plan):
        out = np.asarray(col.materialize())  # corrupted once → healed
    assert np.array_equal(out, truth)
    assert REGISTRY.counter("robust.integrity.read_heals").value == heals0 + 1
    detach_manifest(db.device)
    assert reg_before is None


def test_verified_read_persistent_raises(schema):
    db = _db(schema, "packed")
    attach_manifest(db.device)
    di = db.device.indexes[("DT", "Doc")]
    col = di.dst_col
    bad = np.asarray(col.words).copy()
    bad[0] ^= 1
    col.words = jnp.asarray(bad)
    col._dense = None
    with pytest.raises(IntegrityError) as ei:
        col.materialize()
    assert ei.value.context["table"] == "DT"
    assert ei.value.context["column"] == "__dst__"
    assert not ei.value.retryable


def test_quarantined_read_raises(schema):
    db = _db(schema, "packed")
    attach_manifest(db.device)
    col = db.device.indexes[("DT", "Doc")].dst_col
    col._quarantined = True
    with pytest.raises(IntegrityError):
        col.materialize()
    col._quarantined = False


def test_manifest_detach_restores_zero_overhead(schema):
    db = _db(schema, "packed")
    attach_manifest(db.device)
    detach_manifest(db.device)
    col = db.device.indexes[("DT", "Doc")].dst_col
    assert col._expected_crc is None and not col._quarantined


# ---------------------------------------------------------------------------
# Scrubber: detect → quarantine → heal from snapshot → bit-identical
# ---------------------------------------------------------------------------


def _corrupt_in_place(col):
    bad = np.asarray(col.words).copy()
    bad[bad.shape[0] // 2] ^= 0x01000000
    col.words = jnp.asarray(bad)
    col._dense = None


def test_scrub_detects_and_heals(schema, tmp_path):
    db = _db(schema, "packed")
    eng = GQFastEngine(db)
    ref = np.asarray(eng.prepare(SQL)(t=3))
    snapshot_db(db, str(tmp_path))
    attach_manifest(db.device)

    reg = MetricsRegistry()
    healed_addrs: list[str] = []
    s = Scrubber(db, snapshot_dir=str(tmp_path), cols_per_tick=2,
                 registry=reg, on_heal=healed_addrs.append)
    assert s.scrub_full()["failed"] == 0  # clean pass

    _corrupt_in_place(db.device.indexes[("DT", "Doc")].dst_col)
    stats = s.scrub_full()
    assert stats["healed"] == 1 and stats["failed"] == 0
    assert healed_addrs == ["I_DT.Doc/__dst__"]
    assert reg.counter("robust.integrity.scrub_detected").value == 1
    assert reg.counter("robust.integrity.scrub_repairs").value == 1

    # post-heal: executables must be re-prepared, then results bit-identical
    eng.invalidate_prepared()
    got = np.asarray(eng.prepare(SQL)(t=3))
    assert np.array_equal(got, ref)


def test_scrub_without_snapshot_quarantines(schema):
    """No snapshot to heal from: the column stays quarantined — reads raise
    typed errors instead of serving corrupted data."""
    db = _db(schema, "packed")
    attach_manifest(db.device)
    col = db.device.indexes[("DT", "Doc")].dst_col
    _corrupt_in_place(col)
    reg = MetricsRegistry()
    s = Scrubber(db, snapshot_dir=None, registry=reg)
    stats = s.scrub_full()
    assert stats["failed"] == 1
    assert reg.counter("robust.integrity.scrub_failures").value == 1
    assert col._quarantined
    with pytest.raises(IntegrityError):
        col.materialize()


def test_scrub_memo_corruption_healed_by_drop(schema):
    """A flipped decode memo needs no snapshot: drop it and re-decode."""
    db = _db(schema, "packed")
    attach_manifest(db.device)
    col = db.device.indexes[("DT", "Doc")].dst_col
    truth = np.asarray(col.materialize())
    bad = truth.copy()
    bad[0] ^= 1
    col._dense = jnp.asarray(bad)
    reg = MetricsRegistry()
    s = Scrubber(db, registry=reg)
    s.scrub_full()
    assert reg.counter("robust.integrity.memo_drops").value == 1
    assert col._dense is None
    assert np.array_equal(np.asarray(col.materialize()), truth)


def test_scrub_verify_fault_site_drives_heal(schema, tmp_path):
    """The chaos-lane recipe: a corrupt-mode scrub.verify spec that outlasts
    the scrubber's re-read retries forces a full detect→heal→re-verify cycle
    against truly-intact storage."""
    db = _db(schema, "packed")
    snapshot_db(db, str(tmp_path))
    reg = MetricsRegistry()
    s = Scrubber(db, snapshot_dir=str(tmp_path), registry=reg)
    plan = FaultPlan(seed=5, specs=[FaultSpec("scrub.verify", mode="corrupt",
                                              max_fires=3)])
    with faults.active(plan):
        stats = s.scrub_full()
    assert stats["healed"] == 1 and stats["failed"] == 0
    assert reg.counter("robust.integrity.scrub_repairs").value == 1
    assert s.scrub_full()["failed"] == 0  # clean afterwards


def test_corrupt_scrub_heal_end_to_end(schema, tmp_path):
    """The full durability story on one DB: corrupt two columns in place,
    scrub, and require bit-identical answers afterwards for both encodings'
    query paths."""
    db = _db(schema, "auto")
    eng = GQFastEngine(db)
    refs = {sql: np.asarray(eng.prepare(sql)(t=9)) for sql in (SQL, SQL_SUM)}
    snapshot_db(db, str(tmp_path))
    attach_manifest(db.device)

    di = db.device.indexes[("DT", "Doc")]
    _corrupt_in_place(di.dst_col)
    for col in di.measure_cols.values():
        if hasattr(col, "words"):
            _corrupt_in_place(col)
            break
    reg = MetricsRegistry()
    s = Scrubber(db, snapshot_dir=str(tmp_path), registry=reg)
    stats = s.scrub_full()
    assert stats["healed"] >= 2 and stats["failed"] == 0
    eng.invalidate_prepared()
    for sql, ref in refs.items():
        assert np.array_equal(np.asarray(eng.prepare(sql)(t=9)), ref)


def _corrupt_any(col):
    """Flip one bit of a column's stored bytes, whatever its kind."""
    if hasattr(col, "words"):
        _corrupt_in_place(col)
    else:
        bad = np.asarray(col.array).copy()
        bad[bad.shape[0] // 2] ^= 1 << 20
        col.array = jnp.asarray(bad)


@pytest.mark.parametrize("name", ["__key__", "__dst__", "Fre"])
def test_scrub_heals_a_corrupt_pull_copy_from_its_csr_column(schema, name):
    """The hop kernel reads each index's pull stream, permuted copies of its
    CSR columns: a flip in a copy is detected, quarantined and healed by
    permuting the verified CSR column anew (no snapshot needed), and the
    answers afterwards are bit-identical."""
    db = _db(schema, "auto")
    eng = GQFastEngine(db)
    refs = {sql: np.asarray(eng.prepare(sql)(t=9)) for sql in (SQL, SQL_SUM)}
    man = attach_manifest(db.device)
    assert f"I_DT.Doc/pull/{name}" in man
    col = dict(db.device.indexes[("DT", "Doc")].pull.columns())[name]
    _corrupt_any(col)
    reg = MetricsRegistry()
    healed: list[str] = []
    s = Scrubber(db, snapshot_dir=None, registry=reg, on_heal=healed.append)
    stats = s.scrub_full()
    assert stats["healed"] == 1 and stats["failed"] == 0
    assert healed == [f"I_DT.Doc/pull/{name}"] and not col._quarantined
    eng.invalidate_prepared()
    for sql, ref in refs.items():
        assert np.array_equal(np.asarray(eng.prepare(sql)(t=9)), ref)


def test_pull_copy_of_a_corrupt_csr_column_stays_quarantined(schema):
    """A copy is never re-derived from a CSR column that fails its own
    digest: with both corrupt and no snapshot, both stay quarantined and a
    read of the copy raises IntegrityError instead of serving bad bytes."""
    db = _db(schema, "dense")
    GQFastEngine(db)
    attach_manifest(db.device)
    di = db.device.indexes[("DT", "Doc")]
    _corrupt_any(di.dst_col)
    _corrupt_any(di.pull.src_col)
    s = Scrubber(db, snapshot_dir=None, registry=MetricsRegistry())
    assert s.scrub_full()["failed"] == 2
    assert di.dst_col._quarantined and di.pull.src_col._quarantined
    with pytest.raises(IntegrityError):
        di.pull.src_col.materialize()


def test_pull_streams_built_under_a_manifest_join_it(schema):
    """An engine built on a database that already carries a manifest (a
    restored one) checks the CSR columns before permuting them, and its
    streams join the manifest with verified reads; a flipped copy then
    fails the prepare that would read it."""
    db = _db(schema, "dense")
    attach_manifest(db.device)
    GQFastEngine(db)
    assert db.device.integrity == build_manifest(db.device)
    key = db.device.indexes[("DT", "Doc")].pull.dst
    assert key._addr == ("DT", "Doc", "pull/__key__")
    _corrupt_any(key)
    with pytest.raises(IntegrityError):
        GQFastEngine(db).prepare(SQL)

    bad = _db(schema, "dense")
    attach_manifest(bad.device)
    _corrupt_any(bad.device.indexes[("DT", "Term")].dst_col)
    with pytest.raises(IntegrityError) as ei:
        GQFastEngine(bad)
    assert ei.value.context["column"] == "__dst__"


def test_rebuilt_pull_copy_must_match_a_recorded_digest(schema, tmp_path):
    """A restored database carries the digests its snapshot recorded for the
    pull copies; an engine whose rebuilt copy differs raises IntegrityError
    and leaves the index without a stream rather than with an unchecked
    one."""
    db = _db(schema, "packed")
    GQFastEngine(db)
    snapshot_db(db, str(tmp_path))
    back = restore_db(str(tmp_path))
    assert all(di.pull is None for di in back.device.indexes.values())
    GQFastEngine(back)  # the rebuilt copies match the recorded digests
    assert back.device.integrity == build_manifest(back.device)

    again = restore_db(str(tmp_path))
    again.device.integrity["I_DT.Term/pull/__dst__"]["encoded_crc"] ^= 1
    with pytest.raises(IntegrityError):
        GQFastEngine(again)
    assert again.device.indexes[("DT", "Term")].pull is None


def test_load_column_arrays_verified(schema, tmp_path):
    db = _db(schema, "packed")
    gen_path = snapshot_db(db, str(tmp_path))
    arrays, meta = load_column_arrays(str(tmp_path), 1, "DT", "Doc", "__dst__")
    assert meta["kind"] == "packed"
    assert np.array_equal(
        arrays["words"], np.asarray(db.device.indexes[("DT", "Doc")].dst_col.words)
    )
    # heal reads verify too: flip the words file → IntegrityError
    manifest = json.load(open(os.path.join(gen_path, "MANIFEST.json")))
    spec = manifest["arrays"]["dev/DT.Doc/__dst__/words"]
    f = os.path.join(gen_path, "arrays", spec["file"])
    raw = bytearray(open(f, "rb").read())
    raw[-1] ^= 0x80
    open(f, "wb").write(bytes(raw))
    with pytest.raises(IntegrityError):
        load_column_arrays(str(tmp_path), 1, "DT", "Doc", "__dst__")


# ---------------------------------------------------------------------------
# Hot swap (load_generation)
# ---------------------------------------------------------------------------


def _mini_queries():
    return {"Q": SQL}


def _sample_params(_kind):
    return {"t": 4}


def test_load_generation_warms_and_serves(schema, tmp_path):
    from repro.launch.serve import load_generation

    db = _db(schema, "packed")
    eng = GQFastEngine(db)
    ref = np.asarray(eng.prepare(SQL)(t=4))
    snapshot_db(db, str(tmp_path))
    eng2, prepared, gen = load_generation(
        str(tmp_path), _mini_queries(), _sample_params, bucket=4
    )
    assert gen == 1 and set(prepared) == {"Q"}
    assert np.array_equal(np.asarray(prepared["Q"](t=4)), ref)


def test_load_generation_corrupted_rolls_back(schema, tmp_path):
    """A bad generation raises before any serving state could change — the
    rollback contract is that the caller simply keeps its old references."""
    from repro.launch.serve import load_generation

    db = _db(schema, "packed")
    gen_path = snapshot_db(db, str(tmp_path))
    f = sorted(glob.glob(os.path.join(gen_path, "arrays", "*.npy")))[3]
    raw = bytearray(open(f, "rb").read())
    raw[len(raw) // 2] ^= 0x10
    open(f, "wb").write(bytes(raw))
    with pytest.raises(IntegrityError):
        load_generation(str(tmp_path), _mini_queries(), _sample_params, bucket=4)


# ---------------------------------------------------------------------------
# Atomic writer + retention
# ---------------------------------------------------------------------------


def test_publish_dir_atomic_on_failure(tmp_path):
    from repro.ckpt.atomic import publish_dir

    final = str(tmp_path / "out")

    def bad_write(tmp):
        open(os.path.join(tmp, "partial"), "w").write("x")
        raise RuntimeError("crash mid-write")

    with pytest.raises(RuntimeError):
        publish_dir(final, bad_write)
    assert not os.path.exists(final)  # nothing partially visible
    assert os.listdir(str(tmp_path)) == []  # tmp dir cleaned up

    publish_dir(final, lambda t: open(os.path.join(t, "ok"), "w").write("y"))
    assert os.path.exists(os.path.join(final, "ok"))


def test_retain_stamped(tmp_path):
    from repro.ckpt.atomic import retain_stamped, stamped_name

    for n in (1, 2, 5, 9):
        os.makedirs(tmp_path / stamped_name("gen_", n))
    removed = retain_stamped(str(tmp_path), "gen_", 2)
    assert removed == [1, 2]
    assert sorted(os.listdir(tmp_path)) == [
        stamped_name("gen_", 5), stamped_name("gen_", 9)
    ]


def test_checkpoint_manager_uses_shared_writer(tmp_path):
    """ckpt/manager.py rides the same atomic helper (the refactor half of
    this layer): saves are stamped, retained, and restorable."""
    from repro.ckpt.manager import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": np.arange(6, dtype=np.float32)}
    for step in (1, 2, 3):
        mgr.save(step, tree)
    assert mgr.list_steps() == [2, 3]
    restored, meta = mgr.restore({"w": np.zeros(6, np.float32)})
    assert np.array_equal(np.asarray(restored["w"]), tree["w"])
    assert meta["step"] == 3


# ---------------------------------------------------------------------------
# Thread safety
# ---------------------------------------------------------------------------


def test_counter_concurrent_increments_exact():
    reg = MetricsRegistry()
    c = reg.counter("t.c")
    N, T = 5_000, 8

    def work():
        for _ in range(N):
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(T)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert c.value == N * T  # lost updates would make this flaky-low


def test_histogram_concurrent_observe_exact_count():
    reg = MetricsRegistry()
    h = reg.histogram("t.h")
    N, T = 2_000, 8

    def work():
        for i in range(N):
            h.observe(float(i % 50))

    threads = [threading.Thread(target=work) for _ in range(T)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert h.count == N * T
    assert int(h.counts.sum()) == N * T


def test_registry_concurrent_get_or_create():
    reg = MetricsRegistry()
    out = []

    def work():
        out.append(id(reg.counter("same.name")))

    threads = [threading.Thread(target=work) for _ in range(16)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert len(set(out)) == 1  # everyone got the same object


def test_prepared_cache_concurrent_ops():
    from repro.robust import PreparedCache

    cache = PreparedCache(capacity=8, registry=MetricsRegistry())
    errs = []

    def work(tid):
        try:
            for i in range(2_000):
                cache.put((tid, i % 16), i)
                cache.get((tid, (i * 7) % 16))
                len(cache)
        except BaseException as e:  # OrderedDict corruption raises here
            errs.append(e)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert not errs
    assert len(cache) <= 8


def test_prepared_cache_clear_and_engine_invalidate(schema):
    db = _db(schema, "dense")
    eng = GQFastEngine(db)
    eng.prepare(SQL)
    assert len(eng._cache) == 1
    assert eng.invalidate_prepared() == 1
    assert len(eng._cache) == 0
    eng.prepare(SQL)  # re-prepare works after invalidation


# ---------------------------------------------------------------------------
# Taxonomy
# ---------------------------------------------------------------------------


def test_integrity_error_taxonomy():
    e = IntegrityError("bad bytes", table="DT", key="Doc", column="__dst__",
                       expected_crc=1, actual_crc=2)
    assert isinstance(e, QueryError) and isinstance(e, RuntimeError)
    assert e.code == "INTEGRITY"
    assert not e.retryable  # retrying a corrupted read cannot help
    d = e.to_dict()
    assert d["code"] == "INTEGRITY" and d["context"]["table"] == "DT"


def test_build_manifest_covers_every_column(schema):
    db = _db(schema, "auto")
    man = build_manifest(db.device)
    expect = set()
    for (t, k), di in db.device.indexes.items():
        expect.add(f"I_{t}.{k}/__dst__")
        expect.update(f"I_{t}.{k}/{m}" for m in di.measure_cols)
    assert set(man) == expect
    for dig in man.values():
        assert {"kind", "count", "encoded_crc", "decoded_crc"} <= set(dig)
