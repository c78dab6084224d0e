"""Fragment: the (index, field, view, shard) storage unit.

The reference's fragment is one mmap'd roaring bitmap holding all rows of a
2^20-column shard concatenated at ``pos = row*ShardWidth + col%ShardWidth``
(reference fragment.go:100-159, 3077-3080). Here a fragment is a dense
bitmap tensor:

* **host mirror** ``uint32[capacity, W]`` (numpy) — the authoritative copy.
  Mutations (set/clear/import) are applied here first, giving exact
  changed-bit accounting (the reference gets this from roaring's
  ``Add/Remove`` return values) with zero device round-trips.
* **device copy** ``uint32[capacity+1, W]`` (jax, HBM) — the compute copy,
  synced lazily before queries: a handful of dirty rows go up as a scatter
  update, wholesale changes as a fresh ``device_put``. The extra final row
  is permanently zero so missing row-ids can gather it (avoids dynamic
  shapes under jit).

Row-ids are arbitrary uint64 (the reference allows e.g. hashed ids), so the
row axis is *sparse*: row-id -> slot via a host dict, with capacity grown in
powers of two so jitted kernels see a bounded set of shapes. The column
axis is dense — that asymmetry (sparse rows × dense 2^20-bit columns) is
the central data-layout decision for HBM residency: queries are
row-oriented, and a row is one 128 KiB word vector that XLA streams at HBM
bandwidth.

Write batching replaces the reference's op-log+snapshot cadence
(fragment.go:84 MaxOpN=10000): mutations accumulate in the host mirror and
flush to HBM in one batched update, amortizing transfer exactly the way the
reference amortizes fsyncs.
"""

from __future__ import annotations

import itertools
import threading
import weakref
from contextlib import contextmanager
from typing import Iterable

import numpy as np

import jax
import jax.numpy as jnp

from pilosa_tpu.core import membudget, residency
from pilosa_tpu.ops import _hostops, bitops, kernels
from pilosa_tpu.shardwidth import SHARD_WIDTH, SHARD_WORDS

# BSI row layout within a bsig_* view (reference fragment.go:90-96).
BSI_EXISTS_BIT = 0
BSI_SIGN_BIT = 1
BSI_OFFSET_BIT = 2

# Rows per anti-entropy checksum block (reference fragment.go:81).
HASH_BLOCK_SIZE = 100

_MIN_CAPACITY = 8

# Paranoia mode: invariant checks after every mutation (the analogue of
# the reference's `roaringparanoia` build tag, roaring/roaring_paranoia.go).
import os as _os

PARANOIA = bool(_os.environ.get("PILOSA_TPU_PARANOIA"))


class FragmentInvariantError(AssertionError):
    """Internal coherence violation between slot map, host mirror, and
    device copy (reference Container.check, roaring.go:2967-3028)."""


def _retry_evict(ref) -> None:
    """Complete a deferred HBM eviction from a lock-free thread: blocking
    acquire is safe here because this thread holds no fragment locks."""
    f = ref()
    if f is None:
        return
    with f._lock:
        if f._evict_pending:
            f._evict_pending = False
            f._device = None
            f._dirty.clear()
            f._delta_reset()
            # The flag may be stale: a concurrent device_bits can have
            # re-admitted the copy after the deferral was recorded.  The
            # accounting must follow the copy we just dropped, or the
            # budget over-counts those bytes forever (release is a no-op
            # when the budget already evicted the entry).
            if f._budget_key is not None:
                membudget.default_budget().release(f._budget_key)
            residency.default_tracker().note_dropped(f)


@jax.jit
def _scatter_rows(device_bits, slots, rows):
    return device_bits.at[slots].set(rows)


@jax.jit
def _scatter_words(device_bits, flat_idx, vals):
    """Word-granular device update: flat positions into the row-major
    [capacity+1, W] copy.  Ships 8 bytes per CHANGED WORD instead of a
    whole row per dirty slot — the winning path when a write batch
    touches many rows sparsely (the common ingest shape)."""
    shape = device_bits.shape
    return device_bits.reshape(-1).at[flat_idx].set(vals).reshape(shape)


class Fragment:
    """Dense bitmap tensor for one (index, field, view, shard)."""

    _epoch_counter = itertools.count()

    def __init__(
        self,
        index: str = "",
        field: str = "",
        view: str = "",
        shard: int = 0,
        n_words: int = SHARD_WORDS,
    ):
        self.index = index
        self.field = field
        self.view = view
        self.shard = shard
        self.n_words = n_words
        self.shard_width = n_words * 32

        self._lock = threading.RLock()
        self._slot_of: dict[int, int] = {}  # row id -> slot
        self._rowids: list[int] = []  # slot -> row id
        self._set_host(np.zeros((0, n_words), dtype=np.uint32))
        self._device: jax.Array | None = None
        self._dirty: set[int] = set()
        # word-granular change tracking riding alongside _dirty: flat
        # (slot * n_words + word) indices accumulated per mutation
        # batch; None = degraded (an untracked mutation happened or the
        # delta grew past worthwhile), meaning sync falls back to the
        # row/full paths.  Always cleared together with _dirty.
        # fields are established by _delta_reset below — ONE place owns
        # the reset semantics (including the int32-eligibility degrade)
        self._word_delta: list[np.ndarray] | None = None
        self._word_delta_small: set[int] = set()
        self._word_delta_n = 0
        self._word_delta_compact_at = 0
        self._counts: np.ndarray | None = None  # per-slot cached popcounts
        # (epoch, version)-keyed storage-shape stats (container_profile):
        # /debug/fragments and the flight planner's cost model read these
        # per request, so they must not rescan roaring containers while
        # the fragment is unchanged
        self._container_profile: tuple | None = None
        # Monotonic mutation counter: cheap cache key for stacked-tensor
        # caches built over this fragment (executor batch fast path).
        self.version = 0
        # Process-unique object nonce: a DIFFERENT Fragment later serving
        # the same shard (dropped by resize cleanup, re-created when the
        # shard moves back) must never alias a cached stack's version —
        # both fragments count versions from 0, so the number alone can
        # coincide. Cache keys pair (epoch, version).
        self.epoch = next(self._epoch_counter)
        # op accounting for the storage layer's snapshot trigger
        # (reference fragment.go:84 MaxOpN, 2284-2293).
        self.op_n = 0
        self.on_op = None  # callback(fragment) after mutations
        # optional storage.FragmentFile: mutations append to its op log
        # (reference fragment.go:453 storage.OpWriter). Lock order is
        # always fragment._lock (outer) -> store lock (inner).
        self.store = None
        # HBM accounting key for the device copy (syswrap analogue,
        # membudget); created lazily on first device sync.
        self._budget_key = None
        # set by the budget's evict callback when it could not take the
        # lock; honored at the next device sync
        self._evict_pending = False
        # bytes shipped host->device by the most recent device_bits()
        # sync (0 when the device copy was already current); the ingest
        # uploader reads this for its overlap accounting
        self.last_sync_h2d_bytes = 0
        # residency-tier state owned by core/residency.py's tracker:
        # decayed hit heat, predictive-prefetch flags, and a mirror of
        # the budget's pin bit (authoritative copy lives in membudget)
        self._heat = 0.0
        self._heat_t = 0.0
        self._res_staging = False  # queued on the prefetch uploader
        self._res_prefetched = False  # prefetch paid the upload; unqueried
        self._res_pinned = False
        self._delta_reset()

    def _set_host(self, arr: np.ndarray) -> None:
        """The ONLY way to (re)assign the host mirror: keeps the cached
        base address in lockstep (the latency tier builds 100+ row
        addresses per query off ``_host_addr``; __array_interface__
        costs ~1 us per access vs ~60 ns for the attribute — and a
        reassignment that forgot the pair would hand the native kernel
        a pointer into the freed old buffer)."""
        self._host = arr
        self._host_addr = arr.__array_interface__["data"][0]

    # -- row bookkeeping ----------------------------------------------------

    @property
    def capacity(self) -> int:
        return self._host.shape[0]

    def row_ids(self) -> list[int]:
        """Sorted ids of rows that physically exist (may include all-zero
        rows that were written then cleared — same as the reference, where
        cleared containers linger until snapshot)."""
        with self._lock:
            return sorted(self._slot_of)

    def has_row(self, row: int) -> bool:
        return row in self._slot_of

    def _grow(self, need: int) -> None:
        cap = max(_MIN_CAPACITY, self.capacity)
        while cap < need:
            cap *= 2
        if cap != self.capacity:
            grown = np.zeros((cap, self.n_words), dtype=np.uint32)
            grown[: self.capacity] = self._host
            self._set_host(grown)
            self._drop_device()  # full re-upload on next query

    def _slots_batch(self, row_ids: np.ndarray) -> np.ndarray:
        """Slots for every row id (ascending unique array), creating
        missing ones with ONE capacity grow — a per-row _slot loop
        re-copies the whole mirror at every doubling step during large
        imports (caller holds the lock)."""
        out = np.empty(row_ids.size, dtype=np.int64)
        missing = []
        for i, r in enumerate(row_ids):
            s = self._slot_of.get(int(r))
            if s is None:
                missing.append(i)
            else:
                out[i] = s
        if missing:
            self._grow(len(self._rowids) + len(missing))
            for i in missing:
                r = int(row_ids[i])
                s = len(self._rowids)
                self._slot_of[r] = s
                self._rowids.append(r)
                out[i] = s
            if self._counts is not None:
                self._counts = None
        return out

    def _slot(self, row: int, create: bool = False) -> int | None:
        s = self._slot_of.get(row)
        if s is None and create:
            s = len(self._rowids)
            self._grow(s + 1)
            self._slot_of[row] = s
            self._rowids.append(row)
            if self._counts is not None:
                self._counts = None
        return s

    def _drop_device(self) -> None:
        """Drop the device copy and its budget accounting (caller holds
        the lock); host mirror stays authoritative."""
        self._device = None
        self._dirty.clear()
        self._delta_reset()
        if self._budget_key is not None:
            membudget.default_budget().release(self._budget_key)
        residency.default_tracker().note_dropped(self)

    # -- mutation -----------------------------------------------------------

    def _touch(self, slot: int, tracked: bool = False) -> None:
        """Mark a slot mutated.  ``tracked=True`` promises the caller
        already recorded the exact changed words via _delta_note*; any
        untracked mutation degrades word-granular sync (correct by
        default for future mutation paths)."""
        if not tracked:
            self._delta_degrade()
        self._dirty.add(slot)
        self._counts = None
        self.version += 1
        self.op_n += 1
        if self.on_op is not None:
            self.on_op(self)
        if PARANOIA:
            self.check_invariants()

    # word-delta tracking degrades past this fraction of the fragment's
    # words — a full re-upload is cheaper than a giant scatter
    _WORD_DELTA_MAX_FRACTION = 8

    def _delta_over_budget(self) -> bool:
        """Whether the delta outgrew its budget.  Duplicate notes (the
        same words mutated repeatedly) inflate the raw count, so compact
        to unique positions before deciding to degrade — but only past
        2x budget (hysteresis): compacting at the boundary would re-sort
        the whole delta on every subsequent mutation."""
        budget = (
            max(1, self.capacity) * self.n_words
            // self._WORD_DELTA_MAX_FRACTION
        )
        raw = self._word_delta_n + len(self._word_delta_small)
        if raw <= budget:
            return False
        if self._word_delta_n == 0:
            return True  # the set alone is already unique: genuinely over
        if raw < self._word_delta_compact_at:
            return False  # tolerate duplicates until raw doubles again —
            # a delta parked at ~budget unique positions must not be
            # re-sorted on every subsequent duplicate note
        flat = self._delta_flat()
        self._word_delta = [flat]
        self._word_delta_small = set()
        self._word_delta_n = len(flat)
        self._word_delta_compact_at = 2 * max(len(flat), budget)
        return len(flat) > budget

    def _delta_note(self, flat: np.ndarray) -> None:
        """Record changed flat word positions (slot * n_words + word)
        for the word-granular device sync (caller holds the lock)."""
        if self._word_delta is None:
            return
        if (self.capacity + 1) * self.n_words >= 2**31:
            # the word path's int32 scatter can never serve this
            # fragment; don't accumulate notes it can't use
            self._delta_degrade()
            return
        self._word_delta.append(np.asarray(flat, dtype=np.int64))
        self._word_delta_n += len(flat)
        if self._delta_over_budget():
            self._delta_degrade()

    def _delta_note_word(self, slot: int, word: int) -> None:
        """Single-word note: a plain set add (no per-bit ndarray churn),
        naturally deduped so toggle-heavy workloads on few words don't
        inflate the degrade counter."""
        if self._word_delta is not None:
            self._word_delta_small.add(slot * self.n_words + word)
            if self._delta_over_budget():
                self._delta_degrade()

    def _delta_note_mask(self, slot: int, mask: np.ndarray) -> None:
        """Record every set word of ``mask`` as changed for ``slot``."""
        if self._word_delta is not None:
            w = np.flatnonzero(mask)
            self._delta_note(slot * self.n_words + w.astype(np.int64))

    def _delta_degrade(self) -> None:
        """An untracked or too-large mutation: word-granular sync is off
        until the next device rebuild."""
        self._word_delta = None
        self._word_delta_small = set()
        self._word_delta_n = 0

    def _delta_reset(self) -> None:
        if (self.capacity + 1) * self.n_words >= 2**31:
            # the int32 word scatter can never serve this fragment:
            # don't track notes it can't use (capacity only changes
            # through paths that re-run this reset)
            self._delta_degrade()
            return
        self._word_delta = []
        self._word_delta_small = set()
        self._word_delta_n = 0
        self._word_delta_compact_at = 0

    def _delta_flat(self) -> np.ndarray:
        """All noted word positions, deduped (caller checked not-None)."""
        parts = list(self._word_delta)
        if self._word_delta_small:
            parts.append(
                np.fromiter(
                    self._word_delta_small,
                    dtype=np.int64,
                    count=len(self._word_delta_small),
                )
            )
        if not parts:
            return np.empty(0, np.int64)
        return np.unique(np.concatenate(parts))

    def check_invariants(self, device: bool = False) -> None:
        """Verify slot-map ↔ host-mirror ↔ device-copy coherence; raises
        FragmentInvariantError on violation (reference `ctl check` +
        Container.check, ctl/check.go:47-133, roaring.go:2967-3028).
        ``device=True`` additionally pulls the device copy to host and
        compares every clean row — expensive, test-only."""
        with self._lock:
            if len(self._rowids) != len(self._slot_of):
                raise FragmentInvariantError(
                    f"rowids/slot_of size mismatch: "
                    f"{len(self._rowids)} != {len(self._slot_of)}"
                )
            for r, s in self._slot_of.items():
                if not (0 <= s < len(self._rowids)) or self._rowids[s] != r:
                    raise FragmentInvariantError(
                        f"slot map incoherent at row {r} -> slot {s}"
                    )
            if self._host.shape != (self.capacity, self.n_words):
                raise FragmentInvariantError(
                    f"host mirror shape {self._host.shape} != "
                    f"({self.capacity}, {self.n_words})"
                )
            if len(self._rowids) > self.capacity:
                raise FragmentInvariantError("more rows than capacity")
            if self._host.dtype != np.uint32:
                raise FragmentInvariantError(
                    f"host mirror dtype {self._host.dtype}"
                )
            if self._counts is not None:
                want = np.bitwise_count(
                    self._host[: len(self._rowids)]
                ).sum(axis=1)
                if not np.array_equal(
                    np.asarray(self._counts, dtype=np.int64),
                    want.astype(np.int64),
                ):
                    raise FragmentInvariantError("stale row-count cache")
            if device and self._device is not None:
                dev = np.asarray(self._device)
                if dev.shape != (self.capacity + 1, self.n_words):
                    raise FragmentInvariantError(
                        f"device copy shape {dev.shape}"
                    )
                if dev[self.capacity].any():
                    raise FragmentInvariantError("zero row is not zero")
                clean = [
                    s
                    for s in range(len(self._rowids))
                    if s not in self._dirty
                ]
                if clean and not np.array_equal(
                    dev[clean], self._host[clean]
                ):
                    raise FragmentInvariantError(
                        "device copy diverged from host mirror on clean rows"
                    )

    def _check_persistable(self, row: int) -> None:
        """With a store attached, reject un-persistable row ids BEFORE
        mutating so memory and op log can't diverge."""
        if self.store is not None:
            self.store.check_row(row)

    @contextmanager
    def _batched_store(self):
        """Coalesce one logical mutation's ops into single batch records
        (one locked append instead of one write+flush per bit)."""
        if self.store is None:
            yield
            return
        self.store.begin_batch()
        try:
            yield
        finally:
            self.store.end_batch()

    def _counts_delta(self, counts0, slots, deltas) -> None:
        """Carry the cached per-slot popcounts across a write (caller
        holds the lock and captured ``counts0 = self._counts`` BEFORE
        mutating — _touch/_slot null it), zero-padding for rows created
        by the write.  ``slots``/``deltas`` are a scalar pair (point
        write) or aligned arrays (import batch).  The ranked-cache role
        of reference cache.go:158/fragment.go:698-712: TopN keeps
        serving from maintained counts instead of rescanning."""
        if counts0 is None:
            return
        n = len(self._rowids)
        if len(counts0) < n:
            counts0 = np.concatenate(
                [counts0, np.zeros(n - len(counts0), dtype=np.int64)]
            )
        counts0[slots] += deltas
        self._counts = counts0

    def set_bit(self, row: int, col: int) -> bool:
        """Set bit (row, col-offset); returns True if it changed
        (reference fragment.go:645-713)."""
        with self._lock:
            self._check_persistable(row)
            counts0 = self._counts
            s = self._slot(row, create=True)
            w, b = col >> 5, np.uint32(1 << (col & 31))
            if self._host[s, w] & b:
                return False
            self._host[s, w] |= b
            self._delta_note_word(s, w)
            self._touch(s, tracked=True)
            self._counts_delta(counts0, s, 1)
            if self.store is not None:
                self.store.log_add(row, col)
            return True

    def clear_bit(self, row: int, col: int) -> bool:
        with self._lock:
            s = self._slot(row)
            if s is None:
                return False
            w, b = col >> 5, np.uint32(1 << (col & 31))
            if not self._host[s, w] & b:
                return False
            counts0 = self._counts
            self._host[s, w] &= ~b
            self._delta_note_word(s, w)
            self._touch(s, tracked=True)
            self._counts_delta(counts0, s, -1)
            if self.store is not None:
                self.store.log_remove(row, col)
            return True

    def get_bit(self, row: int, col: int) -> bool:
        with self._lock:
            s = self._slot_of.get(row)
            if s is None:
                return False
            return bool((int(self._host[s, col >> 5]) >> (col & 31)) & 1)

    def rows_with_column(self, col: int) -> list[int]:
        """Row ids containing this column — one vectorized pass over the
        host mirror's column word (the Rows(column=...) filter; reference
        fragment.go:2612-2657 filterColumn, without per-row get_bit)."""
        with self._lock:
            n = len(self._rowids)
            if n == 0:
                return []
            w, b = col >> 5, np.uint32(col & 31)
            mask = (self._host[:n, w] >> b) & np.uint32(1)
            return [self._rowids[s] for s in np.flatnonzero(mask)]

    def set_row_words(self, row: int, words: np.ndarray) -> bool:
        """Replace a whole row (reference fragment.go:781-834 setRow);
        returns True if the row changed."""
        with self._lock:
            self._check_persistable(row)
            s = self._slot(row, create=True)
            words = np.asarray(words, dtype=np.uint32)
            if np.array_equal(self._host[s], words):
                return False
            old = self._host[s].copy()
            self._host[s] = words
            self._delta_note_mask(s, old ^ words)
            self._touch(s, tracked=True)
            # log AFTER applying: a snapshot triggered mid-logging then
            # serializes the new state, against which these ops replay
            # idempotently
            if self.store is not None:
                added = words & ~old
                removed = old & ~words
                with self._batched_store():
                    if added.any():
                        self.store.log_add_mask(row, added)
                    if removed.any():
                        self.store.log_remove_mask(row, removed)
            return True

    def clear_row(self, row: int) -> bool:
        return self.set_row_words(row, np.zeros(self.n_words, dtype=np.uint32))

    def union_row_words(self, row: int, words: np.ndarray) -> int:
        """OR a word vector into a row; returns number of newly-set bits
        (the import-roaring merge unit, reference roaring.go:1463
        ImportRoaringBits)."""
        with self._lock:
            self._check_persistable(row)
            s = self._slot(row, create=True)
            words = np.asarray(words, dtype=np.uint32)
            added_mask = words & ~self._host[s]
            added = bitops.popcount_host(added_mask)
            if added:
                self._host[s] |= words
                self._delta_note_mask(s, added_mask)
                self._touch(s, tracked=True)
                if self.store is not None:
                    self.store.log_add_mask(row, added_mask)
            return added

    def difference_row_words(self, row: int, words: np.ndarray) -> int:
        """ANDNOT a word vector out of a row; returns bits cleared."""
        with self._lock:
            s = self._slot_of.get(row)
            if s is None:
                return 0
            words = np.asarray(words, dtype=np.uint32)
            removed_mask = words & self._host[s]
            removed = bitops.popcount_host(removed_mask)
            if removed:
                self._host[s] &= ~words
                self._delta_note_mask(s, removed_mask)
                self._touch(s, tracked=True)
                if self.store is not None:
                    self.store.log_remove_mask(row, removed_mask)
            return removed

    def import_bits(self, rows: np.ndarray, cols: np.ndarray, clear: bool = False) -> int:
        """Bulk import of (row, col-offset) pairs (reference
        fragment.go:1995-2106 bulkImport). Returns changed-bit count.

        The whole batch is applied as ONE vectorized masked update against
        the host mirror (the role of the reference's container-level merge,
        roaring.go:1463 ImportRoaringBits) — per-row Python work is limited
        to slot bookkeeping and op-log records for rows that changed."""
        rows = np.asarray(rows, dtype=np.uint64)
        cols = np.asarray(cols, dtype=np.int64)
        if rows.size == 0:
            return 0
        with self._lock, self._batched_store():
            counts0 = self._counts  # before slot creation nulls it
            # Group by row directly (never via row*width+col positions,
            # which would wrap uint64 for hashed row ids).
            row_ids = np.unique(rows)
            if clear:
                keep = np.array(
                    [int(r) in self._slot_of for r in row_ids], dtype=bool
                )
                if not keep.any():
                    return 0
                if not keep.all():
                    sel = keep[np.searchsorted(row_ids, rows)]
                    rows = rows[sel]
                    cols = cols[sel]
                    row_ids = row_ids[keep]
                for r in row_ids:  # BEFORE mutation: mirror/WAL atomicity
                    self._check_persistable(int(r))
                slots = np.array(
                    [self._slot_of[int(r)] for r in row_ids], dtype=np.int64
                )
            else:
                for r in row_ids:
                    self._check_persistable(int(r))
                slots = self._slots_batch(row_ids)
            # ONE sort of compact keys drives everything: dedup,
            # per-word grouping, changed-bit detection and WAL
            # positions all fall out — no dense [rows, n_words] mask
            # matrix and no unbuffered ufunc.at scalar loop.  The merge
            # itself is a single native pass when the toolchain exists
            # (hostops.cpp ph_import_merge: the roaring AddN/RemoveN
            # role, reference fragment.go:2052), with the vectorized
            # numpy pipeline as fallback.
            width = self.n_words * 32
            native = None
            if (
                _hostops.load() is not None
                and int(row_ids[-1]) <= (2**62) // width
            ):
                # id-keyed fast path: no inverse/searchsorted pass at
                # all — the native walk binary-searches row_ids once
                # per row run
                key = rows.astype(np.int64) * width + cols
                key.sort()
                native = _hostops.import_merge(
                    key, width, self.n_words, slots, row_ids,
                    self._host, clear, id_keys=True,
                    want_wal=self.store is not None,
                )
            if native is None:
                inverse = np.searchsorted(row_ids, rows)
                key = inverse.astype(np.int64) * width + cols
                key.sort()
                native = _hostops.import_merge(
                    key, width, self.n_words, slots, row_ids,
                    self._host, clear,
                    want_wal=self.store is not None,
                )
            if native is not None:
                n_changed, positions, per_row, changed_word_idx = native
                if n_changed:
                    for i in np.nonzero(per_row)[0]:
                        self._dirty.add(int(slots[i]))
                    if self._word_delta is not None:
                        self._delta_note(changed_word_idx)
                    if self.store is not None:
                        if clear:
                            self.store.log_remove_positions(positions)
                        else:
                            self.store.log_add_positions(positions)
                    self._counts_delta(
                        counts0, slots, -per_row if clear else per_row
                    )
                    self.version += 1
                    self.op_n += int(np.count_nonzero(per_row))
                    if self.on_op is not None:
                        self.on_op(self)
                return int(n_changed)
            ukey = np.unique(key)
            urow = ukey // width  # index into row_ids/slots
            ucol = ukey % width
            bitvals = np.uint32(1) << (ucol & 31).astype(np.uint32)
            # group bits into their words: wkey = urow*n_words + word
            wkey = ukey >> 5
            starts = np.flatnonzero(
                np.r_[True, wkey[1:] != wkey[:-1]]
            )
            wordvals = np.bitwise_or.reduceat(bitvals, starts)
            uw = wkey[starts]
            flat = self._host.reshape(-1)
            flat_idx = slots[uw // self.n_words] * self.n_words + uw % self.n_words
            pre_words = flat[flat_idx]
            if clear:
                changed_words = wordvals & pre_words
                flat[flat_idx] = pre_words & ~wordvals
            else:
                changed_words = wordvals & ~pre_words
                flat[flat_idx] = pre_words | wordvals
            # per-bit changed flags via the pre-update word of each key
            pre_of_key = pre_words[np.searchsorted(uw, wkey)]
            if clear:
                newly = (pre_of_key & bitvals) != 0
            else:
                newly = (pre_of_key & bitvals) == 0
            n_changed = int(np.count_nonzero(newly))
            if n_changed:
                ch_row = urow[newly]
                per_row = np.bincount(ch_row, minlength=len(row_ids))
                changed_idx = np.nonzero(per_row)[0]
                for i in changed_idx:
                    self._dirty.add(int(slots[i]))
                if self._word_delta is not None:
                    self._delta_note(flat_idx[changed_words != 0])
                if self.store is not None:
                    # WAL positions computed directly from the sorted
                    # keys (row-major, ascending — same record order the
                    # mask-unpack path produced); rows were
                    # check_row'd before the mutation above
                    positions = (
                        row_ids[ch_row].astype(np.uint64)
                        * np.uint64(width)
                        + ucol[newly].astype(np.uint64)
                    )
                    if clear:
                        self.store.log_remove_positions(positions)
                    else:
                        self.store.log_add_positions(positions)
                # carry the cached per-slot popcounts across the batch —
                # the per-row changed-bit counts are a by-product of the
                # merge, so TopN keeps serving without a rescan
                # (reference cache.go:158 ranked-cache maintenance)
                self._counts_delta(
                    counts0, slots, -per_row if clear else per_row
                )
                self.version += 1
                self.op_n += len(changed_idx)
                if self.on_op is not None:
                    self.on_op(self)
            return n_changed

    def set_mutex(self, row: int, col: int) -> bool:
        """Mutex-field write: clear col in every other row, set (row, col)
        (reference fragment.go:715-759 setBit w/ mutex vector,
        :3082-3152)."""
        with self._lock, self._batched_store():
            self._check_persistable(row)
            w, b = col >> 5, np.uint32(1 << (col & 31))
            target = self._slot(row, create=True)
            col_word = self._host[:, w]
            holders = np.flatnonzero(col_word & b)
            changed = False
            for s in holders:
                if s != target:
                    # via clear_bit so the op log sees the clears
                    changed |= self.clear_bit(self._rowids[int(s)], col)
            changed |= self.set_bit(row, col)
            return changed

    # -- device sync & query views -----------------------------------------

    def _device_nbytes(self) -> int:
        return (self.capacity + 1) * self.n_words * 4

    def _to_device(self, padded: np.ndarray) -> jax.Array:
        """Upload the compute copy to the device that serves this shard.
        On a multi-device host fragments are dealt over the serving
        mesh's devices by the mesh's one rule (``chip_of_shard``) — every
        field's copy of one shard on the same device, so per-shard Row
        algebra never mixes placements, and on the device that holds the
        shard's slice of every field stack, so a stack's refresh after a
        write stays on that chip — instead of piling every copy (and the
        whole ingest upload stream) onto device 0."""
        from pilosa_tpu.parallel.mesh import chip_of_shard, serving_mesh

        mesh = serving_mesh()
        if mesh is None:
            return jnp.asarray(padded)
        devices = mesh.devices.flat
        return jax.device_put(
            padded, devices[chip_of_shard(self.shard, len(devices))]
        )

    def device_declined(self) -> bool:
        """True when this fragment's full device copy alone would exceed
        the HBM budget cap — callers page rows from the host mirror
        instead of materializing it (the reference's mmap→file fallback,
        syswrap/mmap.go)."""
        return membudget.default_budget().would_decline(self._device_nbytes())

    def _budget_evict_cb(self):
        ref = weakref.ref(self)

        def cb():
            f = ref()
            if f is None:
                return
            # NON-BLOCKING acquire: the evicting thread may hold another
            # fragment's lock (its own admit), and that fragment's evict
            # callback may want ours — blocking here is an AB-BA deadlock
            # between two fragments under concurrent serving threads.
            # When contended, defer AND schedule a retry from a fresh
            # thread (which holds no locks, so a blocking acquire is
            # safe): without the retry, a fragment that is never queried
            # again would keep its HBM copy resident while the budget
            # reports the bytes reclaimed.
            if f._lock.acquire(blocking=False):
                try:
                    f._device = None
                    f._dirty.clear()
                    f._delta_reset()
                    # A concurrent device_bits may have re-admitted the
                    # entry between the budget's pop and this callback;
                    # drop that accounting with the copy (no-op in the
                    # common already-evicted case).
                    if f._budget_key is not None:
                        membudget.default_budget().release(f._budget_key)
                    residency.default_tracker().note_dropped(f)
                finally:
                    f._lock.release()
            else:
                f._evict_pending = True
                t = threading.Timer(0.05, _retry_evict, args=(ref,))
                t.daemon = True
                t.start()

        return cb

    def _account_device(self, rebuilt: bool) -> None:
        """Register/refresh the device copy with the process HBM budget
        (called under self._lock; budget lock nests inside)."""
        budget = membudget.default_budget()
        if self._budget_key is None:
            self._budget_key = membudget.register_owner(self, budget)
        if rebuilt:
            budget.admit(
                self._budget_key, self._device_nbytes(),
                self._budget_evict_cb(), owner=membudget.OWNER_FRAGMENT,
            )
        else:
            budget.touch(self._budget_key)

    def device_bits(self) -> jax.Array:
        """The compute copy ``uint32[capacity+1, W]``; final row is zeros.
        Syncs pending host mutations to HBM first."""
        with self._lock:
            if self._evict_pending:
                self._evict_pending = False
                self._device = None
                self._dirty.clear()
                self._delta_reset()
            # residency outcome: was the compute copy already there when
            # this sync started?  (A dirty-row scatter still counts as a
            # hit — the query didn't pay the cold full upload.)
            was_resident = (
                self._device is not None
                and self._device.shape[0] == self.capacity + 1
            )
            rebuilt = False
            h2d = 0
            if self._device is None or self._device.shape[0] != self.capacity + 1:
                padded = np.zeros((self.capacity + 1, self.n_words), dtype=np.uint32)
                padded[: self.capacity] = self._host
                self._device = self._to_device(padded)
                self._dirty.clear()
                self._delta_reset()
                rebuilt = True
                h2d = padded.nbytes
            elif self._dirty:
                # choose the cheapest transfer: changed words (8 B each),
                # dirty rows (W*4 B each), or the full copy
                flat = None
                if self._word_delta is not None and (
                    (self.capacity + 1) * self.n_words < 2**31
                ):
                    flat = self._delta_flat()
                word_bytes = (
                    bitops.pow2_pad_len(len(flat)) * 8 if flat is not None else None
                )
                # past half the rows dirty, a wholesale device_put beats
                # the row scatter's host gather + jitted update, so the
                # row path's effective cost becomes the full copy
                prefer_full = len(self._dirty) > max(8, self.capacity // 2)
                full_bytes = (self.capacity + 1) * self.n_words * 4
                row_cost = (
                    full_bytes
                    if prefer_full
                    else bitops.pow2_pad_len(len(self._dirty)) * self.n_words * 4
                )
                if (
                    word_bytes is not None
                    and word_bytes <= row_cost
                    # empty delta with dirty slots would mean a tracked
                    # mutation forgot its note — never trust it; the
                    # row/full paths below handle it correctly
                    and len(flat)
                ):
                    idx = np.full(
                        bitops.pow2_pad_len(len(flat)), flat[0], np.int32
                    )
                    idx[: len(flat)] = flat.astype(np.int32)
                    vals = self._host.reshape(-1)[idx]
                    self._device = _scatter_words(
                        self._device, jnp.asarray(idx), jnp.asarray(vals)
                    )
                    h2d = idx.nbytes + vals.nbytes
                elif not prefer_full:
                    slots = np.fromiter(self._dirty, dtype=np.int32)
                    # Pad to a power-of-two bucket so the jitted scatter sees
                    # a bounded set of shapes (duplicate slot writes of the
                    # same data are harmless).
                    padded_slots = np.full(
                        bitops.pow2_pad_len(len(slots)), slots[0], dtype=np.int32
                    )
                    padded_slots[: len(slots)] = slots
                    self._device = _scatter_rows(
                        self._device,
                        jnp.asarray(padded_slots),
                        jnp.asarray(self._host[padded_slots]),
                    )
                    h2d = padded_slots.nbytes + (
                        len(padded_slots) * self.n_words * 4
                    )
                else:
                    padded = np.zeros(
                        (self.capacity + 1, self.n_words), dtype=np.uint32
                    )
                    padded[: self.capacity] = self._host
                    self._device = self._to_device(padded)
                    h2d = padded.nbytes
                self._dirty.clear()
                self._delta_reset()
            self.last_sync_h2d_bytes = h2d
            if h2d:
                kernels.note_transfer(h2d, "h2d")
            self._account_device(rebuilt)
            # hit/miss + heat feed the pin policy; prefetch-thread syncs
            # are accounted as prefetch traffic instead (residency.py)
            residency.default_tracker().note_sync(self, was_resident, h2d)
            return self._device

    def row_device(self, row: int) -> jax.Array:
        """One row's words on device; zeros when the row doesn't exist
        (reference fragment.go:599 ``row`` via roaring OffsetRange).

        When the whole fragment exceeds the HBM budget, only the one
        requested row is shipped (row paging)."""
        with self._lock:
            if self.device_declined():
                return jnp.asarray(self.row_words_host(row))
            bits = self.device_bits()
            s = self._slot_of.get(row, self.capacity)
        return bits[s]

    def rows_device(self, rows: Iterable[int]) -> jax.Array:
        """Gather many rows -> ``uint32[n, W]``; missing rows gather the
        zero row.  Pages just the requested rows when the fragment
        exceeds the HBM budget."""
        rows = list(rows)
        with self._lock:
            if self.device_declined():
                out = np.zeros((len(rows), self.n_words), dtype=np.uint32)
                for i, r in enumerate(rows):
                    s = self._slot_of.get(r)
                    if s is not None:
                        out[i] = self._host[s]
                return jnp.asarray(out)
            bits = self.device_bits()
            slots = np.array(
                [self._slot_of.get(r, self.capacity) for r in rows], dtype=np.int32
            )
        return bits[jnp.asarray(slots)]

    def row_words_host(self, row: int) -> np.ndarray:
        with self._lock:
            s = self._slot_of.get(row)
            if s is None:
                return np.zeros(self.n_words, dtype=np.uint32)
            return self._host[s].copy()

    def row_columns(self, row: int) -> np.ndarray:
        """Sorted column offsets of a row (host materialization)."""
        return bitops.unpack_columns(self.row_words_host(row))

    def rows_matrix_host(self) -> tuple[list[int], np.ndarray]:
        """(row_ids, words[len(row_ids), W]) — one copy of every present
        row in slot order, for bulk consumers (serving-stack builds) that
        would otherwise pay a Python call + copy per row."""
        with self._lock:
            n = len(self._rowids)
            return list(self._rowids), self._host[:n].copy()

    def stack_block(self, slot_of: dict[int, int]):
        """This shard's ``[R, W]`` block of a serving stack whose row axis
        is ``slot_of`` (row id -> position; exec/stacks.py refreshes a
        stack from it after a write), in one locked look.  ``(dev,
        slots)`` where the device copy is at hand: the copy, synced, and
        per position the slot to gather from it — the zero row for a row
        this fragment lacks — so the block never passes through the host
        (the caller sees on which device the copy lies).  Else ``(None,
        block)``, gathered from the host mirror.  None when the fragment
        holds a row the stack has no position for."""
        with self._lock:
            n = len(self._rowids)
            dst = np.fromiter(
                (slot_of.get(r, -1) for r in self._rowids), np.int64, n
            )
            if (dst < 0).any():
                return None
            resident = (
                self._device is not None
                and not self._evict_pending
                and self._device.shape[0] == self.capacity + 1
            )
            if resident:
                slots = np.full(len(slot_of), self.capacity, dtype=np.int32)
                slots[dst] = np.arange(n, dtype=np.int32)
                return self.device_bits(), slots
            block = np.zeros((len(slot_of), self.n_words), dtype=np.uint32)
            block[dst] = self._host[:n]
            return None, block

    def row_count(self, row: int) -> int:
        with self._lock:
            s = self._slot_of.get(row)
            if s is None:
                return 0
            return bitops.popcount_host(self._host[s])

    def row_pair_count(self, ra: int, rb: int, op: str) -> int:
        """Fused ``popcount(op(row_a, row_b))`` from the host mirror,
        zero-copy under the fragment lock — the latency tier for a lone
        ``Count(op(Row, Row))`` (reference roaring.go:568; the batched
        throughput tier is the device gram, ops/kernels.py).  ``op`` is
        one of intersect/union/difference/xor; absent rows count as
        zero rows."""
        with self._lock:
            sa = self._slot_of.get(ra)
            sb = self._slot_of.get(rb)
            if sa is None and sb is None:
                return 0
            if sa is None:
                if op == "difference":
                    return 0
                if op == "intersect":
                    return 0
                return bitops.popcount_host(self._host[sb])
            if sb is None:
                if op == "intersect":
                    return 0
                return bitops.popcount_host(self._host[sa])
            return bitops.pair_count_host(self._host[sa], self._host[sb], op)

    def row_counts(self) -> tuple[list[int], np.ndarray]:
        """(row_ids, per-row popcounts) over existing rows — the TopN
        ranked-cache analogue (reference cache.go).  Counts are
        MAINTAINED across writes (point deltas and import batches carry
        them, like the reference's incremental cache updates,
        fragment.go:698-712) and recomputed from the host mirror only
        when absent — never a device round trip, so a lone TopN stays
        in the latency tier."""
        with self._lock:
            if self._counts is None or len(self._counts) != len(self._rowids):
                n = len(self._rowids)
                self._counts = np.bitwise_count(self._host[:n]).sum(
                    axis=1, dtype=np.int64
                )
            ids = list(self._rowids)
            return ids, self._counts.copy()

    # -- BSI (bit-sliced integer) operations -------------------------------

    def bsi_tensors(self, bit_depth: int):
        """(planes[bit_depth, W], exists, sign) device tensors for BSI
        kernels; missing planes gather zeros."""
        planes = self.rows_device(
            range(BSI_OFFSET_BIT, BSI_OFFSET_BIT + bit_depth)
        )
        exists = self.row_device(BSI_EXISTS_BIT)
        sign = self.row_device(BSI_SIGN_BIT)
        return planes, exists, sign

    def fill_bsi_tensors_host(
        self, bit_depth: int, planes_out, exists_out, sign_out
    ) -> None:
        """Host-mirror twin of :func:`bsi_tensors`: fill CALLER-OWNED
        arrays (planes_out[bit_depth, W], exists_out[W], sign_out[W],
        zero-initialized) from the mirror — the latency tier
        preallocates one stacked buffer for all fragments, so a lone
        cold BSI predicate costs exactly one field-sized host copy."""
        with self._lock:
            for k in range(bit_depth):
                s = self._slot_of.get(BSI_OFFSET_BIT + k)
                if s is not None:
                    planes_out[k] = self._host[s]
            se = self._slot_of.get(BSI_EXISTS_BIT)
            if se is not None:
                exists_out[:] = self._host[se]
            ss = self._slot_of.get(BSI_SIGN_BIT)
            if ss is not None:
                sign_out[:] = self._host[ss]

    def bsi_tensors_host(self, bit_depth: int):
        """(planes[bit_depth, W], exists, sign) numpy copies — the
        allocate-per-fragment convenience over
        :func:`fill_bsi_tensors_host`."""
        planes = np.zeros((bit_depth, self.n_words), dtype=np.uint32)
        exists = np.zeros(self.n_words, dtype=np.uint32)
        sign = np.zeros(self.n_words, dtype=np.uint32)
        self.fill_bsi_tensors_host(bit_depth, planes, exists, sign)
        return planes, exists, sign

    def set_value(self, col: int, bit_depth: int, value: int) -> bool:
        """Write a stored (already base-offset) value for a column
        (reference fragment.go:929-1003 setValueBase)."""
        with self._lock, self._batched_store():
            changed = self.set_bit(BSI_EXISTS_BIT, col)
            mag = abs(value)
            if value < 0:
                changed |= self.set_bit(BSI_SIGN_BIT, col)
            else:
                changed |= self.clear_bit(BSI_SIGN_BIT, col)
            for k in range(bit_depth):
                if (mag >> k) & 1:
                    changed |= self.set_bit(BSI_OFFSET_BIT + k, col)
                else:
                    changed |= self.clear_bit(BSI_OFFSET_BIT + k, col)
            return changed

    def value(self, col: int, bit_depth: int) -> tuple[int, bool]:
        """(stored value, exists) for a column (reference
        fragment.go:894-927)."""
        with self._lock:
            if not self.get_bit(BSI_EXISTS_BIT, col):
                return 0, False
            mag = 0
            for k in range(bit_depth):
                if self.get_bit(BSI_OFFSET_BIT + k, col):
                    mag |= 1 << k
            if self.get_bit(BSI_SIGN_BIT, col):
                mag = -mag
            return mag, True

    def clear_value(self, col: int) -> bool:
        """Remove a column's BSI value entirely — one masked pass over
        the plane rows' column word instead of a per-row clear_bit loop."""
        with self._lock, self._batched_store():
            s_exists = self._slot_of.get(BSI_EXISTS_BIT)
            w, bmask = col >> 5, np.uint32(1 << (col & 31))
            if s_exists is None or not self._host[s_exists, w] & bmask:
                return False
            n = len(self._rowids)
            set_slots = np.flatnonzero(self._host[:n, w] & bmask)
            self._host[set_slots, w] &= ~bmask
            for s in set_slots.tolist():
                self._delta_note_word(int(s), w)
                self._touch(int(s), tracked=True)
                if self.store is not None:
                    self.store.log_remove(self._rowids[s], col)
            return True

    def import_values(self, cols: np.ndarray, values: np.ndarray, bit_depth: int, clear: bool = False) -> None:
        """Bulk BSI import (reference fragment.go:2107-2200 importValue):
        per-plane vectorized writes instead of per-bit loops."""
        cols = np.asarray(cols, dtype=np.int64)
        values = np.asarray(values, dtype=np.int64)
        if cols.size == 0:
            return
        # Last write wins for duplicate columns within a batch (the
        # reference applies batch entries sequentially, same outcome).
        last = len(cols) - 1 - np.unique(cols[::-1], return_index=True)[1]
        cols, values = cols[last], values[last]
        with self._lock, self._batched_store():
            col_words = bitops.pack_columns(cols, self.n_words)
            if clear:
                for row in list(self._slot_of):
                    self.difference_row_words(row, col_words)
                return
            mags = np.abs(values)
            # exists plane: OR in all columns
            self.union_row_words(BSI_EXISTS_BIT, col_words)
            # sign plane: set for negative, clear for non-negative
            neg_words = bitops.pack_columns(cols[values < 0], self.n_words)
            pos_words = col_words & ~neg_words
            self.union_row_words(BSI_SIGN_BIT, neg_words)
            self.difference_row_words(BSI_SIGN_BIT, pos_words)
            for k in range(bit_depth):
                on = bitops.pack_columns(cols[(mags >> k) & 1 == 1], self.n_words)
                off = col_words & ~on
                self.union_row_words(BSI_OFFSET_BIT + k, on)
                self.difference_row_words(BSI_OFFSET_BIT + k, off)

    # -- whole-fragment helpers --------------------------------------------

    def to_host_rows(self) -> dict[int, np.ndarray]:
        """row id -> packed words snapshot (dropping all-zero rows), the
        snapshot payload (reference fragment.go:2325-2381)."""
        with self._lock:
            out = {}
            for row, s in self._slot_of.items():
                if self._host[s].any():
                    out[row] = self._host[s].copy()
            return out

    def snapshot_rows(self) -> tuple[np.ndarray, np.ndarray]:
        """(ascending row ids uint64, stacked words [n, n_words]) — the
        snapshot source as ONE fancy-index copy under the lock
        (to_host_rows + np.stack would copy the mirror twice).
        All-zero rows are kept; they serialize to zero containers."""
        with self._lock:
            rids, slots, host = self.snapshot_source()
            return rids, host[slots]

    def snapshot_source(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(ascending row ids, their slots, the host mirror): what
        :meth:`snapshot_rows` copies, ``host[slots]``, for a caller that
        makes the copy itself once the lock is let go (524 MB for a field
        of 4,000 rows: half a second in which every reader of the
        fragment would wait) and drops it if the fragment was written
        meanwhile (storage/fragmentfile.py: ``mut_seq``, read under this
        lock before and after)."""
        with self._lock:
            rids = np.array(sorted(self._slot_of), dtype=np.uint64)
            slots = np.array(
                [self._slot_of[int(r)] for r in rids], dtype=np.int64
            )
            return rids, slots, self._host

    def load_host_rows(self, rows: dict[int, np.ndarray]) -> None:
        with self._lock:
            self._slot_of.clear()
            self._rowids.clear()
            self._set_host(np.zeros((0, self.n_words), dtype=np.uint32))
            self._drop_device()
            self._counts = None
            self.version += 1
            for row in sorted(rows):
                s = self._slot(row, create=True)
                self._host[s] = np.asarray(rows[row], dtype=np.uint32)
            self.op_n = 0

    def total_count(self) -> int:
        with self._lock:
            return bitops.popcount_host(self._host)

    def all_positions(self) -> np.ndarray:
        """Sorted absolute bit positions row*width + col of every set bit
        (the whole-fragment interchange payload, reference
        fragment.go:2424-2594 WriteTo)."""
        with self._lock:
            parts = []
            width = np.uint64(self.shard_width)
            for row in sorted(self._slot_of):
                cols = bitops.unpack_columns(self._host[self._slot_of[row]])
                if len(cols):
                    parts.append(cols.astype(np.uint64) + np.uint64(row) * width)
            if not parts:
                return np.array([], dtype=np.uint64)
            return np.concatenate(parts)

    def container_profile(self, containers: bool = True) -> dict:
        """Storage-shape stats — set-bit total, bit density, and (when
        ``containers``) the roaring container census — cached under the
        fragment's ``(epoch, version)`` mutation pair, so repeat readers
        (``/debug/fragments``, the flight planner's selectivity model)
        pay a dict lookup instead of a rescan while the fragment is
        unchanged.  ``containers=False`` skips the O(bits) position
        unpack the census needs — the planner prices subtrees on every
        flight, and write-heavy workloads bump versions too often to
        amortize a census per flight; the census is computed lazily and
        folded into the same cached dict on the first full request.
        The whole compute runs under the fragment lock (RLock; the
        helpers retake it) so the cached stats always describe exactly
        one version."""
        from pilosa_tpu.storage import roaring

        with self._lock:
            key = (self.epoch, self.version)
            cached = self._container_profile
            if cached is not None and cached[0] == key:
                prof = cached[1]
            else:
                bits = self.total_count()
                prof = {
                    "bits": bits,
                    "rows": len(self._slot_of),
                    "density": (
                        bits / (len(self._slot_of) * self.shard_width)
                        if self._slot_of
                        else 0.0
                    ),
                }
                self._container_profile = (key, prof)
            if containers and "containers" not in prof:
                prof["containers"] = roaring.container_stats(
                    self.all_positions()
                )
            return prof

    # -- anti-entropy blocks (reference fragment.go:1760-1991) --------------

    def blocks(self) -> list[dict]:
        """Checksums of HashBlockSize-row blocks; blocks with no bits are
        omitted (reference fragment.go Blocks/blockChecksum)."""
        from pilosa_tpu.core import blockhash

        with self._lock:
            by_block: dict[int, list[int]] = {}
            for row in sorted(self._slot_of):
                if self._host[self._slot_of[row]].any():
                    by_block.setdefault(row // HASH_BLOCK_SIZE, []).append(row)
            out = []
            for block in sorted(by_block):
                h = blockhash.new_hash()
                for row in by_block[block]:
                    blockhash.add_row(h, row, self._host[self._slot_of[row]])
                out.append({"id": block, "checksum": h.hexdigest()})
            return out

    def block_data(self, block: int) -> tuple[list[int], list[int]]:
        """(rows, cols) pairs of every set bit in a block, local
        coordinates, row-major (reference fragment.go blockData)."""
        with self._lock:
            rows_out: list[int] = []
            cols_out: list[int] = []
            lo = block * HASH_BLOCK_SIZE
            for row in range(lo, lo + HASH_BLOCK_SIZE):
                slot = self._slot_of.get(row)
                if slot is None:
                    continue
                cols = bitops.unpack_columns(self._host[slot])
                rows_out.extend([row] * len(cols))
                cols_out.extend(int(c) for c in cols)
            return rows_out, cols_out
