"""Tiered fragment residency: tracker policy, flight-driven prefetch,
and the uploader's two-tier priority queue (PR 13).

The working-set manager has three cooperating parts — DeviceBudget
(clock/LRU + pinning, tested in test_membudget.py), ResidencyTracker
(heat, tiers, prefetch accounting), and FlightPrefetcher (flight set ->
field-stack staging on the ingest DeviceUploader).  These tests pin the
policy seams: heat-driven auto-pin, prefetch-context bookkeeping, exact
useful/issued accounting, and ingest-over-prefetch priority.
"""

import threading
import time

import numpy as np
import pytest

from pilosa_tpu.core import membudget, residency
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor


@pytest.fixture()
def clean_residency():
    membudget.configure(None)
    tracker = residency.configure()
    yield tracker
    membudget.configure(None)
    residency.configure()


# ---------------------------------------------------------------------------
# Tracker: tiers, heat, auto-pin
# ---------------------------------------------------------------------------


def test_state_of_reports_tiers(clean_residency):
    tracker = clean_residency
    frag = Fragment(n_words=64)
    frag.set_bit(0, 1)
    assert tracker.state_of(frag) == residency.STATE_HOST
    frag._res_staging = True
    assert tracker.state_of(frag) == residency.STATE_STAGING
    frag.device_bits()
    assert tracker.state_of(frag) == residency.STATE_DEVICE
    frag._res_pinned = True
    assert tracker.state_of(frag) == residency.STATE_PINNED


def test_note_sync_books_hit_and_miss(clean_residency):
    tracker = clean_residency
    frag = Fragment(n_words=64)
    frag.set_bit(0, 1)
    frag.device_bits()  # cold: books a miss
    frag.device_bits()  # warm: books a hit
    snap = tracker.snapshot()
    assert snap["deviceMisses"] == 1
    assert snap["deviceHits"] == 1


def test_heat_accumulates_and_auto_pins(clean_residency):
    membudget.configure(1 << 20)
    tracker = clean_residency
    frag = Fragment(n_words=64)
    frag.set_bit(0, 1)
    for _ in range(12):
        frag.device_bits()
    assert tracker.heat_of(frag) >= tracker.pin_heat - 1
    assert frag._res_pinned
    assert tracker.snapshot()["autoPins"] == 1
    assert membudget.default_budget().is_pinned(frag._budget_key)


def test_heat_decays_toward_zero(clean_residency):
    tracker = residency.configure(heat_half_life=0.05)
    frag = Fragment(n_words=64)
    frag.set_bit(0, 1)
    frag.device_bits()
    frag.device_bits()
    hot = tracker.heat_of(frag)
    time.sleep(0.2)  # 4 half-lives
    assert tracker.heat_of(frag) < hot / 8


def test_drop_clears_tier_flags(clean_residency):
    tracker = clean_residency
    frag = Fragment(n_words=64)
    frag.set_bit(0, 1)
    frag.device_bits()
    frag._res_pinned = True
    frag._drop_device()
    assert not frag._res_pinned
    assert tracker.state_of(frag) == residency.STATE_HOST


# ---------------------------------------------------------------------------
# Prefetch-context bookkeeping: uploads vs query hits, useful accounting
# ---------------------------------------------------------------------------


def test_prefetch_sync_books_upload_not_miss(clean_residency):
    tracker = clean_residency
    frag = Fragment(n_words=64)
    frag.set_bit(0, 1)
    tracker.enter_prefetch()
    try:
        frag.device_bits()
    finally:
        tracker.exit_prefetch()
    snap = tracker.snapshot()
    assert snap["prefetchUploads"] == 1
    assert snap["deviceMisses"] == 0 and snap["deviceHits"] == 0
    # the first QUERY hit on the prefetched copy counts useful
    frag.device_bits()
    snap = tracker.snapshot()
    assert snap["deviceHits"] == 1
    assert snap["prefetchUseful"] == 1


def test_prefetch_of_already_resident_copy_is_wasted(clean_residency):
    tracker = clean_residency
    frag = Fragment(n_words=64)
    frag.set_bit(0, 1)
    frag.device_bits()  # resident via the query path
    tracker.enter_prefetch()
    try:
        frag.device_bits()
    finally:
        tracker.exit_prefetch()
    assert tracker.snapshot()["prefetchWasted"] == 1


def test_maybe_pin_stack_respects_heat_bar(clean_residency):
    tracker = clean_residency
    budget = membudget.configure(1000)
    budget.admit("stack", 100, lambda: None)
    assert not tracker.maybe_pin_stack(budget, "stack", hits=3)
    assert tracker.maybe_pin_stack(budget, "stack", hits=int(tracker.pin_heat))
    assert budget.is_pinned("stack")
    assert tracker.snapshot()["stackPins"] == 1


# ---------------------------------------------------------------------------
# Query -> stack-pair resolution (the prefetcher's oracle)
# ---------------------------------------------------------------------------


def _mini_holder():
    h = Holder()
    idx = h.create_index("i")
    ex = Executor(h)
    rng = np.random.default_rng(5)
    width = h.n_words * 32
    for fname in ("a", "b"):
        idx.create_field(fname)
        writes = [
            f"Set({int(c)}, {fname}={row})"
            for row in (1, 2)
            for c in rng.integers(0, width, size=20)
        ]
        ex.execute("i", " ".join(writes))
    return h, idx, ex


def test_stack_pairs_match_dispatch_matcher(clean_residency):
    from pilosa_tpu import pql
    from pilosa_tpu.server.prefetch import stack_pairs_of_query

    _, idx, _ = _mini_holder()
    # bare Count(Row) rides the segment path: stages nothing
    assert stack_pairs_of_query(idx, pql.parse("Count(Row(a=1))")) == []
    # a real tree stages each leaf's (field, view) pair once
    pairs = stack_pairs_of_query(
        idx, pql.parse("Count(Intersect(Row(a=1), Row(a=2), Row(b=1)))")
    )
    assert ("a", "standard") in pairs and ("b", "standard") in pairs
    assert len(pairs) == 2
    # unknown fields resolve to nothing rather than raising
    assert (
        stack_pairs_of_query(
            idx, pql.parse("Count(Intersect(Row(zz=1), Row(zz=2)))")
        )
        == []
    )


# ---------------------------------------------------------------------------
# DeviceUploader: prefetch lane (priority, dedup, drop-on-full)
# ---------------------------------------------------------------------------


class _Target:
    """Minimal uploadable: records build calls, optional stall."""

    def __init__(self, key, log, stall=0.0):
        self.prefetch_key = key
        self.log = log
        self.stall = stall

    def device_bits(self):
        if self.stall:
            time.sleep(self.stall)
        self.log.append(self.prefetch_key)


def _uploader(slots=2):
    from pilosa_tpu.ingest.pipeline import DeviceUploader

    return DeviceUploader(slots=slots)


def test_uploader_prefetch_dedups_by_key(clean_residency):
    up = _uploader()
    try:
        log = []
        # park the worker on a stalled INGEST sync so the prefetches are
        # judged while still queued (prefetch only rides idle slots)
        up.submit(_Target("hold", log, stall=0.1))
        time.sleep(0.02)
        assert up.submit_prefetch(_Target("k1", log))
        assert not up.submit_prefetch(_Target("k1", log))  # same key: absorbed
        assert up.submit_prefetch(_Target("k2", log))
        assert up.flush(5.0)
        assert log.count("k1") == 1 and log.count("k2") == 1
    finally:
        up.close()


def test_uploader_drops_prefetch_when_queue_full(clean_residency):
    up = _uploader(slots=1)
    try:
        log = []
        # head stalls the worker; the queue (maxsize 8) then fills
        issued = sum(
            1
            for i in range(40)
            if up.submit_prefetch(_Target(f"k{i}", log, stall=0.05))
        )
        assert issued < 40
        assert up.prefetch_dropped > 0
        assert up.flush(30.0)
        assert len(log) == issued
    finally:
        up.close()


def test_uploader_ingest_takes_priority_over_prefetch(clean_residency):
    up = _uploader(slots=1)
    try:
        order = []
        # stall the worker on one prefetch, then queue more prefetches
        # AND an ingest sync; the ingest must jump the prefetch backlog
        up.submit_prefetch(_Target("head", order, stall=0.15))
        for i in range(3):
            up.submit_prefetch(_Target(f"p{i}", order))
        time.sleep(0.02)  # let the worker pick up the stalled head
        ingest = _Target("ingest", order)
        up.submit(ingest)
        assert up.flush(10.0)
        assert order.index("ingest") <= 1  # right after the stalled head
    finally:
        up.close()


# ---------------------------------------------------------------------------
# FlightPrefetcher through the API serving plane
# ---------------------------------------------------------------------------


def test_prefetcher_noops_when_budget_uncapped(clean_residency):
    from pilosa_tpu.server.api import API

    api = API(batch_window=0.002, batch_max_size=8)
    try:
        assert api.prefetcher is not None
        api.create_index("i")
        api.create_field("i", "a")
        api.query("i", "Set(1, a=1)Set(2, a=2)")
        api.query("i", "Count(Intersect(Row(a=1), Row(a=2)))")
        assert residency.default_tracker().snapshot()["prefetchIssued"] == 0
    finally:
        api.close()


def test_prefetcher_stages_and_scores_useful_under_cap(clean_residency):
    from pilosa_tpu.server.api import API

    # rescache off: the usefulness score needs the repeat query to reach
    # the device, not the semantic result cache
    api = API(batch_window=0.003, batch_max_size=32, rescache_entries=0)
    try:
        api.create_index("i")
        rng = np.random.default_rng(9)
        width = api.holder.n_words * 32
        n_fields = 8
        for fi in range(n_fields):
            api.create_field("i", f"f{fi}")
            writes = [
                f"Set({int(c)}, f{fi}={row})"
                for row in (1, 2)
                for c in rng.integers(0, width, size=24)
            ]
            api.query("i", " ".join(writes))
        # one field stack as the executor sizes it: the shard axis is
        # padded up to the mesh's device count before the H2D placement
        import jax

        n_dev = jax.local_device_count()
        stack_bytes = n_dev * 2 * api.holder.n_words * 4
        membudget.configure(3 * stack_bytes + 256)
        tracker = residency.configure()

        def worker(seed):
            import random

            r = random.Random(seed)
            for _ in range(25):
                fi = r.choice((0, 0, 0, 1, 1, r.randrange(n_fields)))
                api.query(
                    "i", f"Count(Intersect(Row(f{fi}=1), Row(f{fi}=2)))"
                )

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(6)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        api.ingest.uploader.flush(5.0)  # trailing prefetch uploads
        snap = tracker.snapshot()
        assert snap["prefetchIssued"] > 0
        assert snap["deviceHits"] > 0
        assert membudget.default_budget().snapshot()["evictions"] > 0

        # deterministic useful accounting: stage one known-cold stack
        # through the prefetcher, let the upload land, then query it —
        # the first query hit on a prefetch-built stack scores useful
        from pilosa_tpu import pql

        idx = api.holder.index("i")
        shard_list = sorted(idx.available_shards())
        cold_fi = next(
            fi
            for fi in range(n_fields)
            if not api.executor.stacks.cached(
                idx.field(f"f{fi}"), shard_list, "standard"
            )
        )
        q = f"Count(Intersect(Row(f{cold_fi}=1), Row(f{cold_fi}=2)))"
        time.sleep(0.06)  # clear the REISSUE_TTL suppression window
        before = tracker.snapshot()["prefetchUseful"]
        assert api.prefetcher.prefetch_flight([("i", pql.parse(q), None)]) == 1
        assert api.ingest.uploader.flush(5.0)
        api.query("i", q)
        assert tracker.snapshot()["prefetchUseful"] > before
    finally:
        api.close()


def test_batcher_calls_prefetcher_hooks(clean_residency):
    from pilosa_tpu import pql
    from pilosa_tpu.server.batcher import QueryBatcher

    class _Exec:
        def execute_batch(self, index, queries):
            return [[0] for _ in queries]

    class _Prefetcher:
        def __init__(self):
            self.query_calls = []
            self.flight_calls = []

        def prefetch_query(self, index, query, shards):
            self.query_calls.append((index, shards))

        def prefetch_flight(self, flights):
            self.flight_calls.append(len(flights))

    pf = _Prefetcher()
    b = QueryBatcher(_Exec(), window=0.005, max_batch=8, prefetcher=pf)
    try:
        b.submit("i", pql.parse("Count(Row(a=1))"), None)
        assert pf.query_calls == [("i", None)]
        assert pf.flight_calls and pf.flight_calls[0] >= 1
    finally:
        b.close()
