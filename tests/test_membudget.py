"""HBM budget manager: LRU accounting + eviction for device copies
(the syswrap/mmap-cap analogue, reference syswrap/mmap.go, holder.go:43).

The integration tests configure a tiny process budget, run Count/TopN
over a holder whose fragments collectively (or individually) exceed it,
and assert the queries still answer correctly with device residency held
under the cap — the reference's "more fragments than mmaps" behavior."""

import numpy as np
import pytest

from pilosa_tpu.core import membudget
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import stacks
from pilosa_tpu.exec.executor import Executor


@pytest.fixture()
def restore_budget():
    yield
    membudget.configure(None)


def test_lru_eviction_order():
    b = membudget.DeviceBudget(100)
    evicted = []
    b.admit("a", 40, lambda: evicted.append("a"))
    b.admit("b", 40, lambda: evicted.append("b"))
    b.touch("a")  # b is now LRU
    b.admit("c", 40, lambda: evicted.append("c"))
    assert evicted == ["b"]
    assert b.used() == 80
    b.admit("d", 90, lambda: evicted.append("d"))
    assert evicted == ["b", "a", "c"]
    assert b.used() == 90


def test_release_does_not_invoke_callback():
    b = membudget.DeviceBudget(100)
    evicted = []
    b.admit("a", 60, lambda: evicted.append("a"))
    b.release("a")
    assert b.used() == 0
    assert evicted == []


def test_admit_replaces_existing_entry():
    b = membudget.DeviceBudget(100)
    b.admit("a", 60, lambda: None)
    b.admit("a", 30, lambda: None)
    assert b.used() == 30
    assert b.entry_count() == 1


def test_oversize_entry_still_admitted_after_evicting_all():
    b = membudget.DeviceBudget(100)
    evicted = []
    b.admit("a", 50, lambda: evicted.append("a"))
    assert b.would_decline(150)
    b.admit("big", 150, lambda: evicted.append("big"))
    assert evicted == ["a"]
    assert b.used() == 150


def test_set_cap_shrink_trims_live_entries():
    # the online oversubscription knob: unlike configure(), shrinking the
    # cap keeps the ledger and evicts cold unpinned entries down to fit
    b = membudget.DeviceBudget(None)
    evicted = []
    for name in ("a", "b", "c"):
        b.admit(name, 40, lambda n=name: evicted.append(n))
    b.pin("c")
    b.touch("b")  # ref bit: "b" deserves a second chance over "a"
    assert b.used() == 120
    b.set_cap(90)
    assert b.cap == 90
    assert b.used() <= 90
    assert "c" not in evicted  # pinned survives the shrink
    assert evicted  # something unpinned was trimmed
    assert b.evictions == len(evicted)
    # growing (or uncapping) evicts nothing further
    before = list(evicted)
    b.set_cap(None)
    assert evicted == before and b.cap is None


def test_set_cap_sheds_pins_past_fraction_of_new_cap():
    # pins granted under a big/absent cap are re-validated on shrink:
    # pinned bytes must fit PIN_MAX_FRACTION of the NEW cap, else the
    # clock scan would have no victims left
    b = membudget.DeviceBudget(None)
    evicted = []
    b.admit("hot", 40, lambda: evicted.append("hot"))
    assert b.pin("hot")  # uncapped: fraction check doesn't apply
    b.admit("warm", 40, lambda: evicted.append("warm"))
    b.set_cap(60)  # fraction limit 30 < 40: the pin must go
    assert not b.is_pinned("hot")
    assert b.unpins == 1
    assert b.used() <= 60
    assert evicted  # the shrink found a victim once the pin released


def test_module_set_cap_mutates_default_budget_in_place():
    prev = membudget.default_budget().cap
    try:
        b = membudget.configure(None)
        b.admit("x", 64, lambda: None)
        assert membudget.set_cap(32) is b  # same ledger, new cap
        assert b.cap == 32 and b.used() <= 32
        membudget.set_cap(None)
        assert b.cap is None
    finally:
        membudget.configure(prev)


def test_owner_gc_releases_entry():
    b = membudget.DeviceBudget(None)

    class Owner:
        pass

    o = Owner()
    key = membudget.register_owner(o, b)
    b.admit(key, 10, lambda: None)
    assert b.used() == 10
    del o
    import gc

    gc.collect()
    assert b.used() == 0


def _build_holder(n_shards=6, n_rows=8, seed=5):
    h = Holder()
    idx = h.create_index("i")
    idx.create_field("f")
    ex = Executor(h)
    rng = np.random.default_rng(seed)
    width = h.n_words * 32
    writes = []
    for row in range(n_rows):
        for col in rng.integers(0, n_shards * width, size=60):
            writes.append(f"Set({int(col)}, f={row})")
    ex.execute("i", " ".join(writes))
    return h, ex


def _truth_pair(h, a, b):
    v = h.index("i").field("f").view("standard")
    return sum(
        int(np.bitwise_count(fr.row_words_host(a) & fr.row_words_host(b)).sum())
        for fr in v.fragments.values()
    )


def _truth_topn(h, n):
    v = h.index("i").field("f").view("standard")
    counts = {}
    for fr in v.fragments.values():
        for r in fr.row_ids():
            c = int(np.bitwise_count(fr.row_words_host(r)).sum())
            if c:
                counts[r] = counts.get(r, 0) + c
    return sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:n]


def test_queries_complete_under_small_cap(restore_budget):
    """Fragments collectively exceed the cap: LRU eviction cycles device
    copies; results stay correct and residency stays capped.

    Lone pair counts and unfiltered TopN are host-tier now (zero device
    residency by design), so the device-cycling queries here are BSI
    aggregates — their per-shard fallback pages fragment tensors
    through the budget."""
    from pilosa_tpu.core.field import FieldOptions

    h, ex = _build_holder()
    idx = h.index("i")
    idx.create_field("v", FieldOptions(field_type="int", min_=0, max_=10**6))
    rng = np.random.default_rng(7)
    width = h.n_words * 32
    vals = {}
    for col in rng.choice(6 * width, size=120, replace=False):
        vals[int(col)] = int(rng.integers(0, 10**6))
    ex.execute("i", " ".join(f"Set({c}, v={x})" for c, x in vals.items()))
    # budget fits ~2.5 BSI fragment tensors, so the 6-shard sweep must
    # admit and EVICT device copies as it pages through
    vview = idx.field("v").view("bsig_v")
    frag_bytes = max(
        f.capacity * f.n_words * 4 for f in vview.fragments.values()
    )
    budget = membudget.configure(int(2.5 * frag_bytes))
    got = ex.execute("i", "Sum(field=v)")[0]
    assert got.value == sum(vals.values()) and got.count == len(vals)
    # host-tier queries still answer correctly with zero device work
    res = ex.execute(
        "i",
        "Count(Intersect(Row(f=0), Row(f=1))) Count(Intersect(Row(f=2), Row(f=3)))",
    )
    assert res == [_truth_pair(h, 0, 1), _truth_pair(h, 2, 3)]
    topn = ex.execute("i", "TopN(f, n=3)")[0]
    assert [(p.id, p.count) for p in topn] == _truth_topn(h, 3)
    assert budget.used() <= budget.cap
    assert budget.evictions > 0


def test_single_fragment_larger_than_cap_pages_rows(restore_budget):
    """BASELINE config-2 shape: one fragment alone exceeds the whole cap;
    row paging answers Count/TopN from the host mirror without ever
    admitting the full fragment."""
    h, ex = _build_holder(n_shards=2, n_rows=16)
    budget = membudget.configure(3 * h.n_words * 4)  # < one fragment
    v = h.index("i").field("f").view("standard")
    assert all(f.device_declined() for f in v.fragments.values())
    res = ex.execute("i", "Count(Intersect(Row(f=0), Row(f=1)))")
    assert res == [_truth_pair(h, 0, 1)]
    topn = ex.execute("i", "TopN(f, n=2)")[0]
    assert [(p.id, p.count) for p in topn] == _truth_topn(h, 2)
    # nothing bigger than the cap was ever admitted
    assert budget.used() <= budget.cap


def test_field_stack_respects_budget_and_evicts(restore_budget):
    h, ex = _build_holder()
    shards = sorted(h.index("i").available_shards())
    field = h.index("i").field("f")
    # generous budget: stack builds and is accounted
    budget = membudget.configure(64 << 20)
    stack = ex.stacks.get(field, shards)
    assert stack is not None
    assert budget.used() > 0
    # tiny budget: stack declines, cache cleared on next eviction pressure
    membudget.configure(1024)
    stacks.drop(field)
    assert ex.stacks.get(field, shards) is None


# ---------------------------------------------------------------------------
# Default cap derivation from accelerator memory stats
# ---------------------------------------------------------------------------


class _FakeDev:
    def __init__(self, platform, stats):
        self.platform = platform
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_default_cap_derived_from_tpu_memory_stats(monkeypatch):
    import pilosa_tpu.core.membudget as mb

    monkeypatch.delenv("PILOSA_TPU_HBM_BUDGET_BYTES", raising=False)
    monkeypatch.setattr(
        "jax.local_devices",
        lambda: [_FakeDev("tpu", {"bytes_limit": 10_000_000_000})],
    )
    monkeypatch.setattr(mb, "_default", None)
    b = mb.default_budget()
    assert b.cap == int(10_000_000_000 * mb.DEFAULT_HBM_FRACTION)


def test_default_cap_sums_every_local_device(monkeypatch):
    """The ledger counts bytes on all local devices (sharded stacks,
    round-robin fragment copies), so the probed cap is their sum."""
    import pilosa_tpu.core.membudget as mb

    monkeypatch.delenv("PILOSA_TPU_HBM_BUDGET_BYTES", raising=False)
    monkeypatch.setattr(
        "jax.local_devices",
        lambda: [_FakeDev("tpu", {"bytes_limit": 10_000_000_000})] * 4,
    )
    monkeypatch.setattr(mb, "_default", None)
    assert mb.default_budget().cap == int(
        4 * 10_000_000_000 * mb.DEFAULT_HBM_FRACTION
    )


def test_default_cap_unlimited_on_cpu(monkeypatch):
    import pilosa_tpu.core.membudget as mb

    monkeypatch.delenv("PILOSA_TPU_HBM_BUDGET_BYTES", raising=False)
    monkeypatch.setattr("jax.local_devices", lambda: [_FakeDev("cpu", {})])
    monkeypatch.setattr(mb, "_default", None)
    assert mb.default_budget().cap is None


def test_env_zero_forces_unlimited_even_on_tpu(monkeypatch):
    import pilosa_tpu.core.membudget as mb

    monkeypatch.setenv("PILOSA_TPU_HBM_BUDGET_BYTES", "0")
    monkeypatch.setattr(
        "jax.local_devices",
        lambda: [_FakeDev("tpu", {"bytes_limit": 10_000_000_000})],
    )
    monkeypatch.setattr(mb, "_default", None)
    assert mb.default_budget().cap is None


def test_env_explicit_cap_wins(monkeypatch):
    import pilosa_tpu.core.membudget as mb

    monkeypatch.setenv("PILOSA_TPU_HBM_BUDGET_BYTES", "12345678")
    monkeypatch.setattr(mb, "_default", None)
    assert mb.default_budget().cap == 12345678


def test_probe_survives_missing_stats(monkeypatch):
    import pilosa_tpu.core.membudget as mb

    monkeypatch.delenv("PILOSA_TPU_HBM_BUDGET_BYTES", raising=False)
    monkeypatch.setattr("jax.local_devices", lambda: [_FakeDev("tpu", None)])
    monkeypatch.setattr(mb, "_default", None)
    assert mb.default_budget().cap is None


# ---------------------------------------------------------------------------
# Clock/second-chance + pinning (the tiered residency policy, PR 13)
# ---------------------------------------------------------------------------


def test_clock_second_chance_spares_referenced_entry():
    b = membudget.DeviceBudget(100)
    evicted = []
    b.admit("a", 40, lambda: evicted.append("a"))
    b.admit("b", 40, lambda: evicted.append("b"))
    # both arrived referenced; a touch keeps "a" referenced through the
    # scan that admits "c" (the scan clears bits as it walks)
    b.touch("a")
    b.admit("c", 40, lambda: evicted.append("c"))
    assert "a" not in evicted
    assert b.used() <= 100


def test_pinned_entry_survives_eviction_storm():
    b = membudget.DeviceBudget(100)
    evicted = []
    b.admit("hot", 40, lambda: evicted.append("hot"))
    assert b.pin("hot")
    for i in range(20):
        b.admit(f"cold{i}", 50, lambda i=i: evicted.append(f"cold{i}"))
    assert "hot" not in evicted
    assert b.is_pinned("hot")
    # pinned bytes tracked exactly
    assert b.pinned_bytes() == 40


def test_pin_declines_past_fraction_of_cap():
    b = membudget.DeviceBudget(100)
    b.admit("a", 40, lambda: None)
    b.admit("b", 40, lambda: None)
    assert b.pin("a")  # 40 <= 50
    assert not b.pin("b")  # 80 > cap * PIN_MAX_FRACTION
    assert b.snapshot()["pinDeclined"] == 1
    # unpin frees headroom for the other
    assert b.unpin("a")
    assert b.pin("b")


def test_pin_absent_key_declines():
    b = membudget.DeviceBudget(100)
    assert not b.pin("ghost")
    assert not b.unpin("ghost")


def test_all_pinned_admits_over_cap():
    b = membudget.DeviceBudget(100)
    b.admit("a", 30, lambda: None)
    # uncapped pin fraction check needs cap; keep under 50
    assert b.pin("a")
    evicted = []
    b.admit("big", 90, lambda: evicted.append("big"))
    # "a" is pinned and nothing else is evictable: over-cap admit
    assert evicted == []
    assert b.used() == 120
    assert b.is_pinned("a")


def test_release_pinned_entry_updates_pinned_bytes():
    b = membudget.DeviceBudget(100)
    b.admit("a", 40, lambda: None)
    b.pin("a")
    b.release("a")
    assert b.pinned_bytes() == 0
    assert b.used() == 0


def test_readmit_preserves_pin():
    b = membudget.DeviceBudget(100)
    b.admit("a", 20, lambda: None)
    b.pin("a")
    b.admit("a", 30, lambda: None)  # capacity grow re-admit
    assert b.is_pinned("a")
    assert b.pinned_bytes() == 30


def test_hit_miss_counters():
    b = membudget.DeviceBudget(100)
    b.admit("a", 10, lambda: None)
    b.touch("a")
    b.touch("a")
    b.touch("ghost")  # absent: not a hit
    snap = b.snapshot()
    assert snap["misses"] == 1 and snap["hits"] == 2


# ---------------------------------------------------------------------------
# Concurrency: threaded admit/touch/release/evict storm with exact
# byte accounting (the lock-free _evict pop race, exec/executor.py)
# ---------------------------------------------------------------------------


def test_concurrent_admit_touch_evict_storm_accounting_exact():
    """Threads admit, touch, pin, and release overlapping keys under a
    tight cap while evictions fire: every key's evict callback runs at
    most once (no double-free), never after its release (no resurrected
    slot), and final used() equals the byte-sum of surviving entries."""
    import threading

    b = membudget.DeviceBudget(2000)
    n_threads, per_thread = 8, 60
    state_lock = threading.Lock()
    # key -> [nbytes, evicted_count, released]
    state = {}

    def evict_cb(key):
        with state_lock:
            state[key][1] += 1

    def worker(ti):
        import random

        r = random.Random(ti)
        for j in range(per_thread):
            key = (ti, j)
            nbytes = r.randint(50, 300)
            with state_lock:
                state[key] = [nbytes, 0, False]
            b.admit(key, nbytes, lambda k=key: evict_cb(k))
            # touch a random earlier key of this thread (may be gone)
            if j:
                b.touch((ti, r.randrange(j)))
            if r.random() < 0.2:
                b.pin(key)
            if r.random() < 0.3:
                k2 = (ti, r.randrange(j + 1))
                b.unpin(k2)
                b.release(k2)
                with state_lock:
                    state[k2][2] = True

    threads = [
        threading.Thread(target=worker, args=(ti,), daemon=True)
        for ti in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    snap = b.snapshot()
    assert snap["evictErrors"] == 0
    with state_lock:
        # no double-free: each key evicted at most once
        assert all(ev <= 1 for _, ev, _ in state.values())
        # exact accounting: used() == bytes of keys neither evicted nor
        # released.  (A release AFTER eviction is a no-op by contract, so
        # released keys are excluded whether or not they were evicted.)
        live = sum(
            nb for nb, ev, rel in state.values() if ev == 0 and rel == 0
        )
    assert b.used() == live
    # pinned accounting consistent with the entries that survived
    assert b.pinned_bytes() <= b.used()


def test_concurrent_stack_cache_hit_vs_evict_no_leak(restore_budget):
    """exec/stacks.py stack-cache storm: concurrent Stacks.get hits
    against budget evictions triggered by other fields' builds must not
    leak budget bytes or resurrect evicted entries — releasing every
    surviving cache entry at the end must zero the budget."""
    import threading

    h = Holder()
    idx = h.create_index("i")
    ex = Executor(h)
    rng = np.random.default_rng(3)
    width = h.n_words * 32
    n_fields = 6
    for fi in range(n_fields):
        idx.create_field(f"f{fi}")
        writes = [
            f"Set({int(c)}, f{fi}={row})"
            for row in (0, 1)
            for c in rng.integers(0, width, size=30)
        ]
        ex.execute("i", " ".join(writes))
    shards = sorted(idx.available_shards())
    stack_bytes = 2 * h.n_words * 4
    budget = membudget.configure(2 * stack_bytes + 64)
    errors = []

    def worker(ti):
        import random

        r = random.Random(ti)
        for _ in range(40):
            field = idx.field(f"f{r.randrange(n_fields)}")
            try:
                ex.stacks.get(field, shards)
            except Exception as e:  # pragma: no cover
                errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(ti,), daemon=True)
        for ti in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert budget.snapshot()["evictErrors"] == 0
    # exact accounting: every surviving entry released -> zero bytes
    for fi in range(n_fields):
        stacks.drop(idx.field(f"f{fi}"))
    assert budget.used() == 0
