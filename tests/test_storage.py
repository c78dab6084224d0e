"""Persistence tests — the reference's Reopen() crash/restart pattern
(test/holder.go:62)."""

import os

import numpy as np
import pytest

from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.storage import roaring
from pilosa_tpu.storage.disk import HolderStore
from pilosa_tpu.storage.fragmentfile import FragmentFile
from pilosa_tpu.shardwidth import SHARD_WIDTH


def make(path):
    h = Holder()
    store = HolderStore(h, str(path))
    store.open()
    return h, store, Executor(h, translator=store.translator)


class TestHolderStore:
    def test_reopen_roundtrip(self, tmp_path):
        h, store, ex = make(tmp_path)
        idx = h.create_index("i")
        idx.create_field("f")
        idx.create_field(
            "v", FieldOptions(field_type="int", min_=-100, max_=100)
        )
        ex.execute("i", "Set(10, f=1)")
        ex.execute("i", f"Set({SHARD_WIDTH + 3}, f=1)")
        ex.execute("i", "Set(10, v=-42)")
        ex.execute("i", 'SetRowAttrs(f, 1, tag="x")')
        ex.execute("i", 'SetColumnAttrs(10, kind="k")')
        store.close()

        h2, store2, ex2 = make(tmp_path)
        assert h2.index("i") is not None
        row = ex2.execute("i", "Row(f=1)")[0]
        assert [int(c) for c in row.columns()] == [10, SHARD_WIDTH + 3]
        assert row.attrs == {"tag": "x"}
        assert h2.field("i", "v").value(10) == (-42, True)
        assert h2.index("i").column_attrs.attrs(10) == {"kind": "k"}
        # existence persisted
        assert ex2.execute("i", "Count(Not(Union()))") == [2]
        store2.close()

    def test_oplog_durable_without_sync(self, tmp_path):
        # mutations must survive a PROCESS crash without close(): WAL
        # appends are flushed to the OS page cache (fsync policy
        # PILOSA_TPU_WAL_FSYNC defaults to the reference's
        # snapshot-only durability; "batch" restores per-batch fsync)
        h, store, ex = make(tmp_path)
        h.create_index("i").create_field("f")
        store.sync()  # schema needs one sync
        ex.execute("i", "Set(5, f=2)")
        ex.execute("i", "Set(6, f=2)")
        ex.execute("i", "Clear(5, f=2)")
        # simulate crash: no close, fresh holder from the same dir
        h2, store2, ex2 = make(tmp_path)
        assert [int(c) for c in ex2.execute("i", "Row(f=2)")[0].columns()] == [6]
        store2.close()

    def test_keys_persist(self, tmp_path):
        h, store, ex = make(tmp_path)
        h.create_index("ki", keys=True).create_field("f", FieldOptions(keys=True))
        ex.execute("ki", 'Set("alpha", f="one")')
        store.close()
        h2, store2, ex2 = make(tmp_path)
        row = ex2.execute("ki", 'Row(f="one")')[0]
        assert row.keys == ["alpha"]
        # same key maps to the same id after reopen
        assert store2.translator.translate_key("ki", "", "alpha") == 1
        store2.close()

    def test_time_views_persist(self, tmp_path):
        h, store, ex = make(tmp_path)
        h.create_index("i").create_field(
            "t", FieldOptions(field_type="time", time_quantum="YMD")
        )
        ex.execute("i", "Set(1, t=9, 2018-03-04T00:00)")
        store.close()
        h2, store2, ex2 = make(tmp_path)
        row = ex2.execute("i", "Range(t=9, 2018-03-01T00:00, 2018-04-01T00:00)")[0]
        assert [int(c) for c in row.columns()] == [1]
        store2.close()

    def test_node_id_stable(self, tmp_path):
        h, store, _ = make(tmp_path)
        nid = store.node_id()
        assert store.node_id() == nid
        h2, store2, _ = make(tmp_path)
        assert store2.node_id() == nid


class TestFragmentFile:
    def test_snapshot_compacts_oplog(self, tmp_path):
        from pilosa_tpu.core.fragment import Fragment

        frag = Fragment("i", "f", "standard", 0)
        path = str(tmp_path / "frag")
        store = FragmentFile(frag, path, snapshot_queue=None)
        store.open()
        for c in range(50):
            frag.set_bit(1, c)
        size_with_ops = os.path.getsize(path)
        store.snapshot()
        assert os.path.getsize(path) < size_with_ops
        assert store.op_n == 0
        # reload
        frag2 = Fragment("i", "f", "standard", 0)
        store2 = FragmentFile(frag2, path)
        store2.open()
        np.testing.assert_array_equal(frag2.row_columns(1), np.arange(50))

    def test_auto_snapshot_over_max_opn(self, tmp_path, monkeypatch):
        import pilosa_tpu.storage.fragmentfile as ff
        from pilosa_tpu.core.fragment import Fragment

        monkeypatch.setattr(ff, "MAX_OP_N", 20)
        frag = Fragment()
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        for c in range(30):
            frag.set_bit(2, c)
        assert store.op_n <= 20  # snapshot reset it at least once

    def test_huge_row_id_persist_raises(self, tmp_path):
        from pilosa_tpu.core.fragment import Fragment

        frag = Fragment()
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        with pytest.raises(ValueError):
            frag.set_bit(2**60, 0)

    def test_mutex_ops_logged(self, tmp_path):
        from pilosa_tpu.core.fragment import Fragment

        frag = Fragment()
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        frag.set_bit(1, 7)
        frag.set_mutex(2, 7)
        frag2 = Fragment()
        store2 = FragmentFile(frag2, str(tmp_path / "frag"))
        store2.open()
        assert not frag2.get_bit(1, 7)
        assert frag2.get_bit(2, 7)

    def test_reference_sample_view_decodes(self):
        # the reference's own sample fragment file (testdata/sample_view/0);
        # decoded read-only (never attach a FragmentFile to the read-only
        # reference mount)
        path = "/root/reference/testdata/sample_view/0"
        if not os.path.exists(path):
            pytest.skip(f"the reference checkout is not mounted: {path}")
        data = open(path, "rb").read()
        positions = roaring.deserialize(data)
        assert len(positions) == 35001
        # round-trip through our serializer preserves the bit set
        np.testing.assert_array_equal(
            roaring.deserialize(roaring.serialize(positions)), positions
        )


class TestStorageReviewRegressions:
    def test_huge_row_rejected_before_mutation(self, tmp_path):
        from pilosa_tpu.core.fragment import Fragment

        frag = Fragment()
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        with pytest.raises(ValueError):
            frag.set_bit(2**60, 3)
        # memory must NOT have been mutated
        assert not frag.get_bit(2**60, 3)
        assert frag.total_count() == 0

    def test_set_row_words_snapshot_mid_log(self, tmp_path, monkeypatch):
        # snapshot triggered while logging a row replacement must not lose
        # the added bits on replay
        import pilosa_tpu.storage.fragmentfile as ff
        from pilosa_tpu.core.fragment import Fragment
        from pilosa_tpu.ops import bitops as bo

        monkeypatch.setattr(ff, "MAX_OP_N", 1)
        frag = Fragment()
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        frag.set_bit(1, 5)
        words = bo.pack_columns(np.array([6]), frag.n_words)
        frag.set_row_words(1, words)
        frag2 = Fragment()
        FragmentFile(frag2, str(tmp_path / "frag")).open()
        np.testing.assert_array_equal(frag2.row_columns(1), [6])

    def test_bsi_value_is_one_batch_record(self, tmp_path):
        from pilosa_tpu.core.fragment import Fragment

        frag = Fragment()
        path = str(tmp_path / "frag")
        store = FragmentFile(frag, path)
        store.open()
        base_size = os.path.getsize(path)
        frag.set_value(3, 16, 0xAAAA)
        data = open(path, "rb").read()
        ops = list(roaring.decode_ops(data, base_size))
        # one add-batch record (clears of unset planes produce nothing)
        assert len(ops) == 1
        assert ops[0][0] == roaring.OP_ADD_BATCH


class TestStorageReviewRegressions2:
    def test_opn_restored_on_reopen(self, tmp_path):
        from pilosa_tpu.core.fragment import Fragment

        frag = Fragment()
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        for c in range(7):
            frag.set_bit(1, c)
        assert store.op_n == 7
        store.close()
        frag2 = Fragment()
        store2 = FragmentFile(frag2, str(tmp_path / "frag"))
        store2.open()
        assert store2.op_n == 7  # restored, so MaxOpN still triggers

    def test_snapshot_worker_survives_failure(self, tmp_path):
        import shutil

        from pilosa_tpu.core.fragment import Fragment
        from pilosa_tpu.storage.fragmentfile import SnapshotQueue

        q = SnapshotQueue(workers=1)
        d = tmp_path / "gone"
        d.mkdir()
        frag = Fragment()
        store = FragmentFile(frag, str(d / "frag"))
        store.open()
        frag.set_bit(1, 1)
        store.close()
        shutil.rmtree(d)  # snapshot will fail: dir removed
        q.enqueue(store)
        q.await_all()  # must not hang
        # worker still alive: a good store snapshot still runs
        frag2 = Fragment()
        store2 = FragmentFile(frag2, str(tmp_path / "ok"), q)
        store2.open()
        frag2.set_bit(1, 1)
        q.enqueue(store2)
        q.await_all()
        assert store2.op_n == 0
        q.stop()

    def test_delete_index_detaches_stores(self, tmp_path):
        h, store, ex = make(tmp_path)
        h.create_index("i").create_field("f")
        ex.execute("i", "Set(1, f=1)")
        frag = h.fragment("i", "f", "standard", 0)
        assert frag.store is not None
        n_before = len(store._stores)
        store.delete_index_dir("i")
        assert frag.store is None
        assert len(store._stores) < n_before


class TestTranslateLog:
    def test_append_and_replay(self, tmp_path):
        from pilosa_tpu.core.translate import TranslateStore
        from pilosa_tpu.storage.translatelog import TranslateLog

        store = TranslateStore()
        log = TranslateLog(store, str(tmp_path / ".keys"))
        log.open()
        assert store.translate_keys("i", "", ["alpha", "beta"]) == [1, 2]
        assert store.translate_keys("i", "f", ["x"]) == [1]
        log.close()

        store2 = TranslateStore()
        log2 = TranslateLog(store2, str(tmp_path / ".keys"))
        log2.open()
        assert store2.translate_keys("i", "", ["alpha", "beta"], create=False) == [1, 2]
        assert store2.translate_id("i", "f", 1) == "x"
        # new allocations continue after the replayed ids
        assert store2.translate_keys("i", "", ["gamma"]) == [3]
        log2.close()

    def test_torn_tail_truncated(self, tmp_path):
        from pilosa_tpu.core.translate import TranslateStore
        from pilosa_tpu.storage.translatelog import TranslateLog

        p = str(tmp_path / ".keys")
        store = TranslateStore()
        log = TranslateLog(store, p)
        log.open()
        store.translate_keys("i", "", ["good"])
        log.close()
        with open(p, "ab") as f:
            f.write(b"\x01\x02")  # torn record
        store2 = TranslateStore()
        log2 = TranslateLog(store2, p)
        log2.open()
        assert store2.translate_key("i", "", "good", create=False) == 1
        # appends after truncation land on a clean record boundary
        assert store2.translate_keys("i", "", ["next"]) == [2]
        log2.close()
        store3 = TranslateStore()
        log3 = TranslateLog(store3, p)
        log3.open()
        assert store3.translate_key("i", "", "next", create=False) == 2
        log3.close()

    def test_holderstore_keys_survive_reopen(self, tmp_path):
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.storage.disk import HolderStore

        h = Holder()
        hs = HolderStore(h, str(tmp_path))
        hs.open()
        h.create_index("ki", keys=True)
        assert hs.translator.translate_keys("ki", "", ["u1", "u2"]) == [1, 2]
        hs.close()

        h2 = Holder()
        hs2 = HolderStore(h2, str(tmp_path))
        hs2.open()
        assert hs2.translator.translate_key("ki", "", "u2", create=False) == 2
        hs2.close()


class TestSnapshotConcurrentWrite:
    """snapshot() encodes from a copied state without the fragment lock;
    an op appended between the copy and the file swap must never be lost
    (the swap retries from fresh state when the monotonic mut_seq
    advanced — op_n can't be the guard, it resets on every swap)."""

    def test_op_landing_mid_encode_survives_reopen(self, tmp_path, monkeypatch):
        from pilosa_tpu.core.fragment import Fragment
        from pilosa_tpu.storage import fragmentfile
        from pilosa_tpu.storage.fragmentfile import FragmentFile

        frag = Fragment(n_words=64)
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        frag.set_bit(1, 10)
        frag.set_bit(2, 20)

        real_serialize = fragmentfile.roaring.serialize_rows
        fired = {"n": 0}

        def racing_serialize(*a):
            # simulate a concurrent writer landing mid-encode, exactly
            # once (the retried snapshot also calls the encoder)
            if fired["n"] == 0:
                fired["n"] += 1
                frag.set_bit(3, 30)
            return real_serialize(*a)

        monkeypatch.setattr(
            fragmentfile.roaring, "serialize_rows", racing_serialize
        )
        store.snapshot()
        monkeypatch.setattr(
            fragmentfile.roaring, "serialize_rows", real_serialize
        )
        store.close()

        frag2 = Fragment(n_words=64)
        store2 = FragmentFile(frag2, str(tmp_path / "frag"))
        store2.open()
        rows = frag2.to_host_rows()
        assert 3 in rows and bool(rows[3][30 // 32] & (1 << (30 % 32)))
        assert 1 in rows and 2 in rows
        store2.close()

    def test_locked_fallback_after_retries(self, tmp_path, monkeypatch):
        """A writer racing every optimistic attempt must not livelock:
        the final attempt rewrites under the fragment lock."""
        from pilosa_tpu.core.fragment import Fragment
        from pilosa_tpu.storage import fragmentfile
        from pilosa_tpu.storage.fragmentfile import FragmentFile

        frag = Fragment(n_words=64)
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        frag.set_bit(1, 10)

        real_serialize = fragmentfile.roaring.serialize_rows
        retries = FragmentFile._SNAPSHOT_RETRIES
        calls = {"n": 0}

        def always_racing(*a):
            # a new op lands during every LOCK-FREE encode (the final,
            # lock-held attempt is the (retries+1)-th encoder call and
            # must not mutate: the caller holds both locks there)
            calls["n"] += 1
            if calls["n"] <= retries:
                frag.set_bit(10 + calls["n"], 5)
            return real_serialize(*a)

        monkeypatch.setattr(
            fragmentfile.roaring, "serialize_rows", always_racing
        )
        store.snapshot()  # must terminate
        monkeypatch.setattr(
            fragmentfile.roaring, "serialize_rows", real_serialize
        )
        assert calls["n"] == retries + 1  # every optimistic attempt raced
        assert store.op_n == 0  # rewrite completed
        store.close()


    def test_the_copy_holds_no_lock_and_a_write_landing_in_it_survives(
        self, tmp_path, monkeypatch
    ):
        """The mirror is copied after the fragment's lock is let go (a
        reader of the fragment does not wait for it), and a write that
        lands between taking the source and the copy is in the file."""
        import threading

        from pilosa_tpu.core.fragment import Fragment
        from pilosa_tpu.storage.fragmentfile import FragmentFile

        frag = Fragment(n_words=64)
        store = FragmentFile(frag, str(tmp_path / "frag"))
        store.open()
        frag.set_bit(1, 10)
        real_source, real_encode = Fragment.snapshot_source, FragmentFile._encode_rows
        free, sources = [], []

        def racing_source(self):
            out = real_source(self)
            sources.append(out)
            if len(sources) == 1:
                frag.set_bit(2, 20)  # lands once the source is taken
            return out

        def watched_encode(self, rids, rwords):
            def probe():
                got = frag._lock.acquire(blocking=False)
                free.append(got)
                if got:
                    frag._lock.release()

            t = threading.Thread(target=probe)
            t.start()
            t.join()
            return real_encode(self, rids, rwords)

        monkeypatch.setattr(Fragment, "snapshot_source", racing_source)
        monkeypatch.setattr(FragmentFile, "_encode_rows", watched_encode)
        store.snapshot()
        monkeypatch.undo()
        assert free == [True, True]  # the raced attempt, then the clean one
        assert len(sources) == 2 and store.op_n == 0
        store.close()

        frag2 = Fragment(n_words=64)
        store2 = FragmentFile(frag2, str(tmp_path / "frag"))
        store2.open()
        rows = frag2.to_host_rows()
        assert bool(rows[1][0] & (1 << 10)) and bool(rows[2][0] & (1 << 20))
        store2.close()


class TestAttrBlockPersistence:
    """Block-wise attr persistence (reference boltdb/attrstore.go:37-90:
    per-bucket writes + LRU read cache, replacing whole-JSON rewrites)."""

    def test_flush_writes_only_dirty_blocks(self, tmp_path):
        import os

        from pilosa_tpu.core.attrs import ATTR_BLOCK_SIZE
        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.storage.disk import HolderStore

        h = Holder()
        store = HolderStore(h, str(tmp_path / "d"))
        store.open()
        idx = h.create_index("i")
        idx.column_attrs.set_attrs(5, {"a": 1})
        idx.column_attrs.set_attrs(5 + 3 * ATTR_BLOCK_SIZE, {"b": 2})
        store.sync()
        attrs_dir = tmp_path / "d" / "i" / ".attrs"
        assert sorted(os.listdir(attrs_dir)) == ["b0.json", "b3.json"]
        m0 = os.path.getmtime(attrs_dir / "b0.json")
        # dirty only block 3 -> block 0's file untouched by the flush
        import time

        time.sleep(0.02)
        idx.column_attrs.set_attrs(7 + 3 * ATTR_BLOCK_SIZE, {"c": 3})
        store.sync()
        assert os.path.getmtime(attrs_dir / "b0.json") == m0
        # clearing every id in a block removes its file
        idx.column_attrs.set_attrs(5, {"a": None})
        store.sync()
        assert sorted(os.listdir(attrs_dir)) == ["b3.json"]
        store.close()

    def test_reopen_loads_lazily_and_legacy_migrates(self, tmp_path):
        import json
        import os

        from pilosa_tpu.core.holder import Holder
        from pilosa_tpu.storage.disk import HolderStore

        d = str(tmp_path / "d")
        h = Holder()
        store = HolderStore(h, d)
        store.open()
        idx = h.create_index("i")
        idx.create_field("f")
        idx.column_attrs.set_attrs(1, {"city": "sfo"})
        h.field("i", "f").row_attrs.set_attrs(9, {"kind": "x"})
        store.sync()
        store.close()

        # drop a LEGACY whole-store file alongside to prove migration
        legacy = {"42": {"legacy": True}}
        with open(os.path.join(d, "i", ".attrs.json"), "w") as f:
            json.dump(legacy, f)

        h2 = Holder()
        store2 = HolderStore(h2, d)
        store2.open()
        idx2 = h2.index("i")
        # legacy file migrated into blocks and removed
        assert not os.path.exists(os.path.join(d, "i", ".attrs.json"))
        assert idx2.column_attrs.attrs(42) == {"legacy": True}
        assert h2.field("i", "f").row_attrs.attrs(9) == {"kind": "x"}
        # ids 1 and 42 share block 0: migrating the legacy id must MERGE
        # into the existing b0.json, not clobber id 1's attrs (ADVICE r4)
        assert idx2.column_attrs.attrs(1) == {"city": "sfo"}
        store2.close()

    def test_flush_dirty_failure_keeps_blocks_dirty(self):
        """A failed write_blocks must leave the dirtied blocks dirty so
        the NEXT flush persists them (ADVICE r4: drain-then-write lost
        attrs forever when the write raised)."""
        import pytest

        from pilosa_tpu.core.attrs import ATTR_BLOCK_SIZE, AttrStore

        class FlakyBackend:
            def __init__(self):
                self.blocks = {}
                self.fail = True

            def load_block(self, bid):
                return self.blocks.get(bid)

            def block_ids(self):
                return list(self.blocks)

            def write_blocks(self, blocks):
                if self.fail:
                    raise OSError("disk full")
                self.blocks.update(
                    {
                        bid: {str(k): v for k, v in data.items()}
                        for bid, data in blocks.items()
                    }
                )

        be = FlakyBackend()
        s = AttrStore(backend=be, cache_blocks=2)
        s.set_attrs(5, {"a": 1})
        s.set_attrs(3 * ATTR_BLOCK_SIZE, {"b": 2})
        with pytest.raises(OSError):
            s.flush_dirty()
        assert be.blocks == {}  # nothing persisted...
        assert s._dirty == {0, 3}  # ...and nothing forgotten
        # reads during the failed window still serve the new values
        assert s.attrs(5) == {"a": 1}
        be.fail = False
        s.flush_dirty()
        assert s._dirty == set()
        assert be.blocks[0]["5"] == {"a": 1}
        assert be.blocks[3][str(3 * ATTR_BLOCK_SIZE)] == {"b": 2}
        # flush with nothing dirty is a no-op (writer not called)
        be.fail = True
        s.flush_dirty()

    def test_lru_eviction_bounded_and_correct(self):
        from pilosa_tpu.core.attrs import ATTR_BLOCK_SIZE, AttrStore

        class MemBackend:
            def __init__(self):
                self.blocks = {}

            def load_block(self, bid):
                return self.blocks.get(bid)

            def block_ids(self):
                return list(self.blocks)

            def write_blocks(self, blocks):
                self.blocks.update(
                    {
                        bid: {str(k): v for k, v in data.items()}
                        for bid, data in blocks.items()
                    }
                )

        be = MemBackend()
        s = AttrStore(backend=be, cache_blocks=4)
        for i in range(10):
            s.set_attrs(i * ATTR_BLOCK_SIZE, {"n": i})
        # flush everything to the backend; cache shrinks to the cap
        s.flush_dirty()
        assert len(s._blocks) <= 4
        # every id still readable (evicted blocks reload from backend)
        for i in range(10):
            assert s.attrs(i * ATTR_BLOCK_SIZE) == {"n": i}
        assert sorted(s.ids()) == [i * ATTR_BLOCK_SIZE for i in range(10)]


class TestWalFsyncPolicy:
    """PILOSA_TPU_WAL_FSYNC: "snapshot" (default, reference durability
    parity — op appends never fsync, only snapshot files do) vs "batch"
    (fsync every WAL batch)."""

    def _count_fsyncs(self, monkeypatch, policy):
        import pilosa_tpu.storage.fragmentfile as ff
        from pilosa_tpu.core.fragment import Fragment

        calls = {"n": 0}
        real = ff.os.fsync

        def counting(fd):
            calls["n"] += 1
            return real(fd)

        monkeypatch.setattr(ff.os, "fsync", counting)
        monkeypatch.setattr(ff, "WAL_FSYNC", policy)
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            frag = Fragment(n_words=64)
            store = ff.FragmentFile(frag, os.path.join(d, "frag"))
            store.open()  # attaches itself as frag.store
            rng = np.random.default_rng(3)
            for _ in range(4):  # 4 WAL batches, no snapshot (< MAX_OP_N)
                frag.import_bits(
                    rng.integers(0, 4, size=50).astype("uint64"),
                    rng.integers(0, 64 * 32, size=50).astype("uint64"),
                )
            before_snapshot = calls["n"]
            store.snapshot()
            after_snapshot = calls["n"]
            store.close()
        return before_snapshot, after_snapshot

    def test_snapshot_policy_skips_wal_fsync(self, monkeypatch):
        wal, total = self._count_fsyncs(monkeypatch, "snapshot")
        assert wal == 0  # op appends: page cache only (reference parity)
        assert total >= 1  # the snapshot file IS fsynced

    def test_batch_policy_fsyncs_every_batch(self, monkeypatch):
        wal, total = self._count_fsyncs(monkeypatch, "batch")
        assert wal >= 4  # one per WAL batch at least
        assert total > wal


class TestCloseDurability:
    """A clean close() under the default 'snapshot' policy must fsync
    the op-log tail: ops appended since the last snapshot live only in
    the page cache, and a power cut right after shutdown would lose
    them (regression for the unflushed-tail review finding)."""

    def test_close_fsyncs_oplog_tail(self, tmp_path, monkeypatch):
        import pilosa_tpu.storage.fragmentfile as ff
        from pilosa_tpu.core.fragment import Fragment

        monkeypatch.setattr(ff, "WAL_FSYNC", "snapshot")
        path = str(tmp_path / "frag")
        frag = Fragment(n_words=64)
        store = ff.FragmentFile(frag, path)
        store.open()
        rng = np.random.default_rng(7)
        frag.import_bits(
            rng.integers(0, 4, size=80).astype("uint64"),
            rng.integers(0, 64 * 32, size=80).astype("uint64"),
        )

        # From here on, only bytes of `path` that were durable at an
        # fsync survive the "crash" — mirror them into durable[].
        real_fsync = os.fsync
        durable = {"img": b""}

        def tracking(fd):
            real_fsync(fd)
            if os.path.exists(path) and os.path.samestat(
                os.fstat(fd), os.stat(path)
            ):
                with open(path, "rb") as fh2:
                    durable["img"] = fh2.read()

        monkeypatch.setattr(ff.os, "fsync", tracking)
        expect = frag.snapshot_rows()
        store.close()

        live = open(path, "rb").read()
        assert durable["img"] == live and len(live) > 0

        # "Power cut" after the clean close: restore the durable image
        # and reopen — every imported bit must still be there.
        with open(path, "wb") as fh:
            fh.write(durable["img"])
        frag2 = Fragment(n_words=64)
        store2 = ff.FragmentFile(frag2, path)
        store2.open()
        got = frag2.snapshot_rows()
        assert np.array_equal(got[0], expect[0])
        assert np.array_equal(got[1], expect[1])
        store2.close()
