"""The device form of a field: the ``[shards, rows, words]`` stack every
batch lane reads, and what is cached against it.

How a stack is keyed, built, laid over the serving mesh, refreshed after
a write, admitted to and evicted from the HBM budget, and which host-side
values derived from one snapshot (grams, row counts, BSI aggregates) may
be served in its place.  The executor's lanes (exec/executor.py) ask here
and decide which lane takes a call; nothing outside this module reads a
:class:`Stack`'s fields except ``slot_of`` and ``bits``.

A field's serving state (:class:`_FieldState`) hangs on the ``Field``
object under one key of its instance dict, so executors wrapping the same
holder share it and a ``cluster/meshexec.py`` facade field keeps its own.
The counters are per executor: :class:`Stacks`.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import weakref

import numpy as np

import jax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from pilosa_tpu.core import membudget, residency
from pilosa_tpu.core.field import Field
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.obs import devledger, qprofile, tracing
from pilosa_tpu.ops import kernels
from pilosa_tpu.parallel import mesh as mesh_mod

# Largest stacked [S, R, W] tensor a lane will materialize: one device's
# share of a serving stack (the whole of it without a mesh); tuned for
# v5e HBM.  It is why the benchmark's one-chip configuration holds 8
# shards (4,000 rows x 8 x 128 KiB).
STACK_BUDGET_BYTES = 4 << 30
# stacks kept per field; two so alternating shard arguments (or a set
# field's standard view beside its BSI view) don't evict each other
# every call
CACHE_ENTRIES = 2
# incremental refresh only pays when few shards changed; past this
# fraction a single bulk re-upload wins
INCR_MAX_FRACTION = 0.5
# Fields up to this many rows may get their FULL gram computed and
# cached with the stack — the reference's ranked cache analogue
# (cache.go): repeat Count(op(Row,Row)) batches against an unchanged
# field then answer from host memory with zero device work.
GRAM_CACHE_MAX_ROWS = 1024
# subset-gram computations against one stack snapshot before the full
# gram pays for itself (write-interleaved workloads never invest)
GRAM_CACHE_MIN_REUSE = 2
# live cross-gram slots kept per stack (one per partner field); each
# full gram is <= 8 MiB host memory at GRAM_CACHE_MAX_ROWS
CROSS_GRAM_SLOTS = 4
# scalar aggregates kept per BSI stack snapshot (sum + min/max + repeat
# range-count bounds; each entry is a handful of ints)
BSI_AGG_SLOTS = 128

# Device cost ledger site for executor-owned launches: stack uploads
# here and, in exec/executor.py, the BSI predicate/aggregate dispatches
# that don't funnel through the kernels dispatch notes (those book under
# ops.kernels / ops.bsi).
DL_STACK = devledger.site("executor.stack_launch")

# the one key of ``vars(field)`` this module owns
_FIELD_KEY = "_serving_stacks"
# monotonic use stamps for LRU eviction (shared across executors —
# stamps only compare within one field's entries)
_lru_clock = itertools.count()


def _put_gathered(bits, si, dev, slots):
    """Shard ``si`` of a stack := rows ``slots`` of a fragment's device
    copy (a position the fragment has no row for gathers its zero row)."""
    return lax.dynamic_update_index_in_dim(bits, dev[slots], si, 0)


def _put_block(bits, si, block):
    """Shard ``si`` of a stack := ``block``."""
    return lax.dynamic_update_index_in_dim(bits, block, si, 0)


# The stack is donated: the write goes into the array that is there.  One
# program a stack shape whatever the number of changed shards (a refresh
# writes them one by one, ``si`` traced).  Over a mesh the two take ONE
# chip's buffer of the stack (:class:`_Writing`), so they run on that
# chip alone and compile once a chip.
_PUT_GATHERED = jax.jit(_put_gathered, donate_argnums=0)
_PUT_BLOCK = jax.jit(_put_block, donate_argnums=0)
# a block gathered where the fragment's copy lies, to be sent to the chip
# that keeps it (the ``peer`` route)
_GATHER = jax.jit(lambda dev, slots: dev[slots])


def positions(shards, bits) -> list[tuple[int, int]]:
    """``(position, shard)`` for every shard of ``shards`` on the shard
    axis of a stack laid out as the array ``bits`` is (the stack's own
    array, or whatever a kernel made of it before any pull): position and
    index in ``shards`` agree on one device only; over a mesh the axis
    follows ``mesh.stack_order``, and a padded position holds no shard.
    Everything that reads or fills the shard axis per shard (a filter's
    segments going in, per-shard result words coming out) goes through
    here."""
    layout = kernels.shards_axis_of(bits)
    n_dev = 1 if layout is None else layout[0].shape[layout[1]]
    return [
        (p, s)
        for p, s in enumerate(mesh_mod.stack_order(tuple(shards), n_dev))
        if s is not None
    ]


class _Writing:
    """A stack's array while a refresh writes into it, taken apart into
    the buffer each chip holds (on one device the array is its one part),
    so that a write is a program of the one chip that keeps the shard.
    Nothing is copied: the parts ARE the array's buffers (a part is
    donated to its program, and what the program returns takes its
    place), and the array is put together from them again
    (``make_array_from_single_device_arrays``).  The array it was made from
    is spent once a part was written: its other buffers live on in the
    new one, untouched."""

    def __init__(self, bits):
        self.shape, self.sharding = bits.shape, bits.sharding
        self.meshed = kernels.shards_axis_of(bits) is not None
        # the buffers in the order of the shard axis
        self.parts = [bits] if not self.meshed else [
            sh.data for sh in sorted(
                bits.addressable_shards,
                key=lambda sh: sh.index[0].indices(self.shape[0])[0],
            )
        ]
        # positions of the shard axis a chip holds
        self.share = self.shape[0] // len(self.parts)

    def home(self, chip: int):
        """The device that keeps ``chip``'s share."""
        return next(iter(self.parts[chip].devices()))

    def put(self, chip: int, put, at: int, *args) -> None:
        """Position ``at`` of ``chip``'s share, by a program of that chip."""
        with DL_STACK.launch(
            sig=f"refresh {self.parts[chip].shape}"
        ), kernels.enqueue("stack_refresh"):
            self.parts[chip] = put(self.parts[chip], np.int32(at), *args)

    def whole(self):
        return (
            jax.make_array_from_single_device_arrays(
                self.shape, self.sharding, self.parts
            ) if self.meshed else self.parts[0]
        )


# per thread: [depth, {stacks on lease}] of the open scope, or None
_tls = threading.local()


class reading(contextlib.ContextDecorator):
    """The lease scope of the calling thread, ``with reading():`` or
    ``@reading()``: every stack whose ``bits`` the thread reads inside it
    is on lease to it until the outermost scope of the thread ends (a
    scope opened inside another joins it).  The executor opens one around
    each batch lane and each per-call execution, ``Stacks.prefetch`` one
    of its own: a scope ends once its launches are enqueued and never
    spans a write of the same request."""

    def __enter__(self):
        scope = getattr(_tls, "scope", None)
        if scope is None:
            scope = _tls.scope = [0, set()]
        scope[0] += 1
        return self

    def __exit__(self, *exc):
        scope = _tls.scope
        scope[0] -= 1
        if not scope[0]:
            _tls.scope = None
            for stack in scope[1]:
                with stack._lock:
                    stack._leased -= 1
        return False


def _held(stack: "Stack") -> bool:
    """Whether the calling thread's open scope has ``stack`` on lease."""
    scope = getattr(_tls, "scope", None)
    return scope is not None and stack in scope[1]


class Stack:
    """One view of a field over one shard list, on the device.

    ``slot_of`` maps row id to position on the row axis and never changes
    while the stack lives; ``bits`` is the current device snapshot.  A
    caller reads ``bits`` once and hands ``(stack, bits)`` to whatever
    caches against it: a derived value is served only for exactly the
    snapshot it was computed from.

    **The snapshot rule.**  An incremental refresh after a write goes IN
    PLACE: the changed shards are written into the array that is there
    by a program the array is donated to, so the old snapshot is deleted
    (``is_deleted()``) and a new array object over the same memory takes
    its place.  Who reads ``bits`` inside a lease scope
    (:class:`reading`) holds the stack on lease until the scope ends,
    and a leased stack is never refreshed in place: another thread's
    refresh then copies (out of place, as every refresh did before;
    ``Stacks.refresh_out_of_place``), and the leaseholder's own scope is
    handed the stack as it holds it (:meth:`Stacks.get`).  Lease and
    refresh both run under the field's lock, so neither can slip
    between the other's check and act.  A launch already enqueued on a
    snapshot outlives its donation (the runtime orders the write after
    the reads), which is why a scope may end before its results are
    pulled.  ``bits`` read outside any scope (tests, tools) is valid
    only until the next refresh."""

    __slots__ = (
        "name", "versions", "slot_of", "bkey", "lru", "hits", "pinned",
        "prefetched", "_lock", "_snap", "_leased",
    )

    def __init__(self, name, versions, slot_of, bits, bkey, prefetched, lock):
        self.name = name  # the field's
        self.versions = versions
        self.slot_of = slot_of
        # Each stack carries its OWN budget key (two per field may be
        # live; one shared key would undercount), released whenever the
        # stack is dropped.
        self.bkey = bkey
        self.lru = next(_lru_clock)
        # use-stamp hit count feeds the pin policy: a stack this hot is
        # exempted from budget eviction (core/residency.py)
        self.hits = 0
        self.pinned = False
        self.prefetched = prefetched
        self._lock = lock  # the field's
        # (snapshot, {name: {key: value}}): the device array and every
        # value derived from it, swapped together in one statement
        self._snap = (bits, {})
        # scopes that hold the stack on lease (under the field's lock)
        self._leased = 0

    @property
    def bits(self):
        scope = getattr(_tls, "scope", None)
        if scope is None or self in scope[1]:
            return self._snap[0]
        with self._lock:
            self._leased += 1
            scope[1].add(self)
            return self._snap[0]

    def refresh(self, bits, versions) -> None:
        """A new snapshot; every derived value and reuse count of the old
        one goes with it.  Snapshot before versions: a racing reader
        keyed on versions must never see the old array."""
        self._snap = (bits, {})
        self.versions = versions

    def get(self, name: str, bits, key=None):
        """The value installed under ``name`` (and ``key``) for exactly
        this snapshot, else None."""
        snap, store = self._snap
        if snap is not bits:
            return None
        slots = store.get(name)
        if not slots:
            return None
        value = slots.get(key)
        if value is not None and len(slots) > 1:
            # most-recent-last, so a bounded put drops the coldest key
            with self._lock:
                cur = slots.pop(key, None)
                if cur is not None:
                    slots[key] = cur
        return value

    def put(self, name: str, bits, value, key=None, cap=None) -> bool:
        """Install ``value`` if ``bits`` is still the snapshot (the
        field's lock is what a refresh holds too), most-recent-last and
        at most ``cap`` keys a name, coldest dropped first; None removes.
        Whether the snapshot was current."""
        with self._lock:
            snap, store = self._snap
            if snap is not bits:
                return False
            slots = store.setdefault(name, {})
            slots.pop(key, None)
            if value is not None:
                slots[key] = value
            while cap is not None and len(slots) > cap:
                slots.pop(next(iter(slots)))
            return True

    def reused(self, name: str, bits, key=None) -> bool:
        """Whether this snapshot has computed a part of ``name``
        GRAM_CACHE_MIN_REUSE times already — observed reuse: the whole now
        pays for itself.  Counts this time if not."""
        with self._lock:
            n = self.get("misses:" + name, bits, key) or 0
            if n < GRAM_CACHE_MIN_REUSE:
                self.put("misses:" + name, bits, n + 1, key)
            return n >= GRAM_CACHE_MIN_REUSE


class _FieldState:
    """What serving keeps per ``Field``."""

    __slots__ = ("lock", "entries", "gram_host", "single_demand")

    def __init__(self):
        # one re-entrant lock a field: lookups, builds, refreshes and
        # every install of a derived value hold it
        self.lock = threading.RLock()
        self.entries: dict[tuple, Stack] = {}
        # ((versions, rows), gram) of the last full gram: it outlives the
        # device stack, so a budget-evicted field re-staged with
        # UNCHANGED fragment versions reattaches it ([R, R] host-tier
        # metadata, tiny) with zero device work — under oversubscription
        # the bytes churn, the derived artifacts shouldn't
        # (docs/residency.md)
        self.gram_host = None
        # lone queries seen per lane before the lane invests in a stack
        self.single_demand: dict[str, int] = {}


def _state(field: Field) -> _FieldState:
    """Fields are shared between executors wrapping the same holder;
    setdefault on the instance dict is atomic."""
    state = vars(field).get(_FIELD_KEY)
    if state is None:
        state = vars(field).setdefault(_FIELD_KEY, _FieldState())
    return state


def _key(shards: list[int], view: str, n_fixed_rows: int | None, mesh):
    """Cache key of a stack. The mesh is part of the key: a device-set/
    configure_serving change must invalidate stacks built with the old
    sharding. View + row-axis length too: the standard and BSI stacks of
    one field share the entries, and a BSI depth autogrow must build a
    fresh (wider) stack. ``mesh`` is the one the caller lays the stack
    out with, so key and layout can never disagree."""
    return (mesh, tuple(shards), view, n_fixed_rows)


def note_single(field: Field, lane: str) -> int:
    """One more lone query of ``lane`` against a cold field; the count."""
    state = _state(field)
    with state.lock:
        n = state.single_demand.get(lane, 0) + 1
        state.single_demand[lane] = n
    return n


def reset_single(field: Field, lane: str) -> None:
    """Restart ``lane``'s warm-up (same lock as :func:`note_single`'s
    read-modify-write, or a concurrent increment could overwrite it)."""
    state = _state(field)
    with state.lock:
        state.single_demand[lane] = 0


def _retire(entries: dict, key, budget) -> None:
    """Drop one stack and give its bytes back (the budget's ``_evict``
    pops lock-free, so the key may be gone already)."""
    old = entries.pop(key, None)
    if old is not None:
        budget.release(old.bkey)


def drop(field: Field) -> None:
    """Retire every cached stack of ``field``."""
    state = vars(field).get(_FIELD_KEY)
    if state is not None:
        with state.lock:
            for k in list(state.entries):
                _retire(state.entries, k, membudget.default_budget())


class Stacks:
    """An executor's way to the stacks of its holder's fields, and its
    count of what that cost (``/debug/vars`` ``serving_cache``)."""

    def __init__(self):
        # stack maintenance accounting (tested: incremental refresh must
        # replace full re-uploads on write-interleaved workloads)
        self.rebuilds = 0
        self.incremental = 0
        # what the incremental refreshes wrote on the device (the changed
        # shards' blocks), what of it was gathered on the host and
        # shipped (0 where the fragments' device copies were the
        # source), what of it one chip sent another (0 where the copies
        # lie on the chip that holds the shard's slice), and how many had
        # to copy the stack because a reader held its snapshot on lease
        self.refresh_bytes = 0
        self.refresh_host_bytes = 0
        self.refresh_peer_bytes = 0
        self.refresh_out_of_place = 0
        # pair counts answered from the cached host gram (zero device
        # work — the serving mode for repeat sequential queries)
        self.gram_hits = 0
        # TopN row-count vectors served from the per-snapshot host cache
        self.rowcount_hits = 0
        # GroupBy combination matrices served from the cached cross gram
        self.crossgram_hits = 0
        # unfiltered BSI Sum/Min/Max scalars served per snapshot
        self.bsi_agg_hits = 0
        # stacks not built: one device's share past STACK_BUDGET_BYTES,
        # the HBM budget's decline, or (the executor's lane choice counts
        # it) a cold field that fewer than two calls of the flight read
        self.refusals = dict.fromkeys(
            ("array_budget", "hbm_budget", "demand"), 0
        )

    # ------------------------------------------------------------- lookups

    @staticmethod
    def cached(
        field: Field, shards: list[int], view: str = VIEW_STANDARD,
        n_fixed_rows: int | None = None,
    ) -> bool:
        """Whether a stack for this (field, shards) is already live — a
        peek that never builds and never takes the field's lock."""
        state = vars(field).get(_FIELD_KEY)
        if state is None or not state.entries:
            return False
        key = _key(shards, view, n_fixed_rows, mesh_mod.serving_mesh())
        return key in state.entries

    @classmethod
    def bsi_cached(cls, field: Field, shards: list[int]) -> bool:
        """:meth:`cached` for the field's BSI stack, beside :meth:`bsi`
        the ONE place spelling its key shape."""
        return cls.cached(
            field, shards, field.bsi_view_name(), 2 + field.bit_depth
        )

    def bsi(self, field: Field, shards: list[int]) -> Stack | None:
        """The raw ``uint32[S, depth+2, W]`` stacked BSI tensor (rows:
        exists=0, sign=1, planes 2.., reference fragment.go:90-96) or
        None (no view / over budget): the same budget-accounted,
        incrementally-refreshed, mesh-sharded stack as a standard view's
        with the row axis pinned to the BSI layout, so every
        Range/Sum/Min/Max batches all shards into one launch (reference
        fragment.go:1271-1534 runs the same scan per fragment)."""
        return self.get(
            field, shards, field.bsi_view_name(),
            fixed_rows=range(2 + field.bit_depth),
        )

    def get(
        self, field: Field, shards: list[int], view: str = VIEW_STANDARD,
        fixed_rows: range | None = None,
    ) -> Stack | None:
        """The stack of one of the field's views, DENSE over ``shards``
        (all-zero slices where a shard has no fragment, so stacks of
        different fields share the shard axis — the GroupBy cross-field
        kernel needs that alignment). With more than one device visible
        the stack is laid out over the serving mesh —
        NamedSharding(mesh, P("shards")) with the shard axis padded to
        the mesh size — so every batched kernel runs on all chips (the
        reference's shard→node mapReduce, executor.go:2454, as a static
        placement).

        ``fixed_rows`` pins the row axis to position-aligned slots (the
        BSI layout) instead of the union of observed row ids.

        Maintenance is INCREMENTAL: when cached fragment versions drift
        but the row set is unchanged, only the changed shards' row blocks
        are written into the device stack, in place (:meth:`_refresh`),
        instead of re-uploading the whole field — the write-batch
        analogue of the reference applying ops to an mmap'd fragment in
        place (fragment.go:2284-2293).  A stack the calling thread's
        scope already holds on lease is handed back as it is held
        (:class:`Stack`, the snapshot rule).  None when over budget or
        empty."""
        v = field.view(view)
        if v is None:
            return None
        frags = {s: v.fragments[s] for s in shards if s in v.fragments}
        if not frags:
            return None
        # key and layout must use the SAME resolved mesh: resolving twice
        # would let a concurrent configure_serving cache an old-mesh
        # layout under the new mesh's key
        mesh = mesh_mod.serving_mesh()
        cache_key = _key(
            shards, view, None if fixed_rows is None else len(fixed_rows), mesh
        )
        versions = tuple(
            # (epoch, version): a re-created fragment (resize drop +
            # re-own) restarts version at 0, so the number alone could
            # alias a cached stack; the epoch pins the object identity
            (frags[s].epoch, frags[s].version) if s in frags else (-1, -1)
            for s in shards
        )
        budget = membudget.default_budget()
        state = _state(field)
        with state.lock:
            entries = state.entries
            stack = entries.get(cache_key)
            if stack is not None:
                if _held(stack):
                    return stack  # as the scope holds it: the snapshot rule
                # LRU: stamp the stack on every hit; eviction below drops
                # the min-stamp one.  A stamp (vs dict pop/reinsert)
                # leaves the budget's lock-free _evict pop as the only
                # writer that removes keys, so no KeyError/resurrection
                # race between a hit and a concurrent eviction.  The
                # budget touch doubles as the clock reference bit — use
                # stamps, not insertion order, drive its eviction scan —
                # and a hot enough stack graduates to a budget pin so an
                # oversubscribed tail can't evict the zipfian head.
                stack.lru = next(_lru_clock)
                stack.hits += 1
                tracker = residency.default_tracker()
                fresh = stack.versions == versions
                try:
                    current = fresh or self._refresh(
                        field, stack, frags, shards, versions
                    )
                except BaseException:
                    # a write that failed may have consumed the array it
                    # was donated
                    _retire(entries, cache_key, budget)
                    raise
                if current:
                    budget.touch(stack.bkey)
                    if not tracker.in_prefetch():
                        tracker.note_stack_hit()
                        tracker.note_hit(stack.prefetched)
                        stack.prefetched = False
                        if fresh and not stack.pinned and (
                            tracker.maybe_pin_stack(
                                budget, stack.bkey, stack.hits
                            )
                        ):
                            stack.pinned = True
                    elif fresh:
                        # the prefetch thread found it already resident:
                        # the query (or an earlier prefetch) beat it here
                        tracker.note_prefetch_wasted()
                    else:
                        # a refresh shipped only the drifted shards; the
                        # NEXT query's hit still credits the prefetch
                        stack.prefetched = True
                        tracker.note_prefetch_upload(0)
                    return stack
                _retire(entries, cache_key, budget)
                # the build below uploads the successor: the retired
                # array must not live on in this frame beside it
                del stack

            with tracing.start_span("executor.stackBuild").set_tag(
                "field", field.name
            ) as sp:
                return self._build(
                    field, frags, shards, fixed_rows, mesh, cache_key,
                    versions, budget, state, sp,
                )

    @reading()
    def prefetch(
        self, field: Field, shards: list[int], view: str = VIEW_STANDARD
    ) -> None:
        """Build (or refresh) the field's stack off the dispatch path —
        the residency prefetcher's target (server/prefetch.py).  Runs on
        the uploader thread inside the tracker's prefetch context, so
        :meth:`get` books the transfer as prefetch traffic rather than a
        query miss; a stack the budget declines is simply not built (the
        dispatch falls back exactly as before).

        The derived serving artifacts ride along: a re-staged stack's
        pair-count gram is reattached or recomputed here too, so an
        evicted-then-prefetched field serves its next flight from the
        host gram with zero device work instead of paying the gram launch
        inside the dispatch."""
        stack = self.get(field, shards, view)
        if stack is None:
            return
        bits = stack.bits
        if bits.shape[1] > GRAM_CACHE_MAX_ROWS:
            return
        if self._gram_at_hand(field, stack, bits) is None:
            self._gram_invest(field, stack, bits)

    # ---------------------------------------------------- build and refresh

    def _build(
        self, field: Field, frags, shards: list[int], fixed_rows, mesh,
        cache_key, versions, budget, state: _FieldState, span,
    ) -> Stack | None:
        """The miss path of :meth:`get`, under the field's lock and the
        ``executor.stackBuild`` span: gather the rows on the host,
        upload, retire what the new stack replaces, admit."""
        if fixed_rows is not None:
            row_ids = list(fixed_rows)
        else:
            row_ids = sorted(
                {r for f in frags.values() for r in f.row_ids()}
            )
        if not row_ids:
            return None
        n_dev = 1 if mesh is None else mesh.devices.size
        # a chip's share of the axis holds the shards whose fragment
        # copies lie on that chip, padded so the mesh divides the axis
        order = mesh_mod.stack_order(tuple(shards), n_dev)
        S, R, W = len(order), len(row_ids), field.n_words
        nbytes = S * R * W * 4
        # the array limit holds against ONE device's share (the shard
        # axis is split over the mesh); the budget's cap is the sum of
        # the chips, so it judges the whole
        refused = (
            "array_budget" if nbytes // n_dev > STACK_BUDGET_BYTES
            else "hbm_budget" if budget.would_decline(nbytes)
            else None
        )
        if refused is not None:
            # over HBM budget: callers fall back to per-fragment paths,
            # which page rows under the same budget (membudget)
            self.refusals[refused] += 1
            span.set_tag("refused", refused)
            return None
        slot_of = {r: i for i, r in enumerate(row_ids)}

        def host_rows(lo: int, hi: int) -> np.ndarray:
            """Stack positions ``lo..hi`` of the shard axis, gathered on
            the host (a position that holds no shard is the mesh's
            padding)."""
            block = np.zeros((hi - lo, R, W), dtype=np.uint32)
            for si in range(lo, hi):
                f = frags.get(order[si])
                if f is None:
                    continue
                # bulk matrix copy, not one Python call per row
                ids, matrix = f.rows_matrix_host()
                src = [
                    k for k, r in enumerate(ids) if r in slot_of
                ]  # fixed_rows: ignore strays
                if src:
                    dst = [slot_of[ids[k]] for k in src]
                    block[si - lo, dst] = matrix[src]
            return block

        span.set_tag("bytes", nbytes).set_tag("devices", n_dev).set_tag(
            "bytes_per_device", nbytes // n_dev
        )
        if mesh is None:
            dev = kernels.h2d(host_rows(0, S))
        else:
            # a device's share at a time: the host never holds the whole
            # array (8.4 GB for 4,000 rows over 16 full-width shards)
            sharding = NamedSharding(mesh, PartitionSpec("shards", None, None))
            slabs = [
                kernels.h2d(
                    host_rows(*index[0].indices(S)[:2]),
                    SingleDeviceSharding(d),
                )
                for d, index in sharding.addressable_devices_indices_map(
                    (S, R, W)
                ).items()
            ]
            dev = jax.make_array_from_single_device_arrays(
                (S, R, W), sharding, slabs
            )
        self.rebuilds += 1
        kernels.note_transfer(nbytes, "h2d", dl_site=DL_STACK)
        qprofile.incr("stack_rebuilds")
        entries = state.entries
        # a BSI depth autogrow (or a standard view's row-set change)
        # retires same-(mesh, shards, view) entries with a different
        # row-axis length — they can never be hit again and would
        # otherwise strand a full device stack under a dead key
        for stale in [
            k for k in entries
            if k[:3] == cache_key[:3] and k[3] != cache_key[3]
        ]:
            _retire(entries, stale, budget)
        while len(entries) >= CACHE_ENTRIES:
            # the budget's _evict pops lock-free, so snapshot-scan and
            # pop with defaults; retry when a concurrent pop races us
            try:
                lru_key = min(
                    entries,
                    key=lambda k: getattr(entries.get(k), "lru", -1),
                )
            except (RuntimeError, ValueError):
                continue  # dict mutated mid-scan; re-check the bound
            _retire(entries, lru_key, budget)  # least recently used
        bkey = object()
        weakref.finalize(field, budget.release, bkey)
        tracker = residency.default_tracker()
        prefetched = tracker.in_prefetch()
        if prefetched:
            # built off the dispatch path by the residency
            # prefetcher: the first query hit counts it useful
            tracker.note_prefetch_upload(nbytes)
        else:
            tracker.note_miss()
        stack = Stack(
            field.name, versions, slot_of, dev, bkey, prefetched, state.lock
        )
        entries[cache_key] = stack

        def _evict(fref=weakref.ref(field), ck=cache_key):
            f = fref()
            if f is not None:
                # lock-free atomic pop: the evicting thread may hold a
                # different field's lock (AB-BA risk); a reader holding
                # a reference to the popped stack just keeps using its
                # (still-valid) device array
                st = vars(f).get(_FIELD_KEY)
                if st is not None:
                    st.entries.pop(ck, None)

        budget.admit(
            bkey, nbytes, _evict, owner=f"stack_{field.field_type}"
        )
        return stack

    def _refresh(
        self, field: Field, stack: Stack, frags, shards: list[int], versions
    ) -> bool:
        """Write the changed shards of a cached stack into the array that
        is there (under the field's lock); False when a full rebuild is
        needed (row set grew, or too many shards drifted).

        Each changed shard's ``[R, W]`` block goes in by one program the
        stack is donated to, so a refresh needs the block beside the
        stack and never a second stack.  Three routes, by where the
        fragment's own copy lies (``Fragment.stack_block``):

        * ``device``: on the chip that holds the shard's slice of the
          stack, which is where ``mesh.chip_of_shard`` puts both.  The
          block is gathered there and written into that chip's buffer of
          the stack: no host gather, nothing shipped, and over a mesh the
          other chips take no part.
        * ``peer``: on another chip (a shard list the rule could not
          align, ``mesh.stack_order``; a copy made under another mesh).
          Gathered where the copy lies and sent to the one chip that keeps
          it.
        * ``host``: no device copy at hand.  Gathered from the host mirror
          and uploaded to the one chip that keeps it.

        Only a stack some reader holds on lease is copied first, by the
        runtime and by no program of its own, and the copy is written as
        any stack is."""
        changed = [
            si for si, (a, b) in enumerate(zip(stack.versions, versions))
            if a != b
        ]
        if not changed or len(changed) > max(
            1, int(len(shards) * INCR_MAX_FRACTION)
        ):
            return False
        if any(frags.get(shards[si]) is None for si in changed):
            return False
        slot_of = stack.slot_of
        bits = stack._snap[0]  # not ``.bits``: that would take a lease
        pos_of = {s: p for p, s in positions(shards, bits)}
        block_bytes = bits.nbytes // bits.shape[0]
        leased = bool(stack._leased)
        if leased:
            # a leased snapshot stays as it is: the writes go into a copy
            # the runtime makes
            bits = jax.device_put(bits, may_alias=False)
        into = _Writing(bits)
        del bits  # spent with the first write
        written = host_bytes = peer_bytes = 0
        with tracing.start_span("stacks.refresh").set_tag(
            "field", field.name
        ).set_tag("shards", len(changed)) as sp:
            try:
                for si in changed:
                    src = frags[shards[si]].stack_block(slot_of)
                    if src is None:
                        return False  # new row: shape change, full rebuild
                    dev, rows = src
                    pos = pos_of[shards[si]]
                    chip, at = divmod(pos, into.share)
                    home = into.home(chip)
                    if dev is None:
                        host_bytes += rows.nbytes
                        block = kernels.h2d(rows, SingleDeviceSharding(home))
                        into.put(chip, _PUT_BLOCK, at, block)
                    elif dev.devices() == {home}:
                        into.put(chip, _PUT_GATHERED, at, dev, rows)
                    else:
                        block = jax.device_put(_GATHER(dev, rows), home)
                        peer_bytes += block.nbytes
                        into.put(chip, _PUT_BLOCK, at, block)
                    written += 1
            finally:
                if written:
                    # the donated array is gone: what was written is the
                    # snapshot now, under the new versions if all of it was
                    stack.refresh(
                        into.whole(),
                        versions if written == len(changed)
                        else stack.versions,
                    )
                sp.set_tag("bytes", written * block_bytes).set_tag(
                    "route",
                    "host" if host_bytes else "peer" if peer_bytes
                    else "device",
                )
                self.refresh_bytes += written * block_bytes
                self.refresh_host_bytes += host_bytes
                self.refresh_peer_bytes += peer_bytes
                kernels.note_transfer(host_bytes, "h2d", dl_site=DL_STACK)
        self.refresh_out_of_place += leased
        self.incremental += 1
        qprofile.incr("stack_incremental")
        return True

    # ------------------------------------------------------ derived values

    @staticmethod
    def _gram_at_hand(field: Field, stack: Stack, bits):
        """The snapshot's full gram if no launch is needed for it: cached
        with the stack, or the field's host copy reattached when versions
        and row count are those it was computed from."""
        g = stack.get("gram", bits)
        if g is None:
            host = _state(field).gram_host
            if host is not None and host[0] == (
                stack.versions, bits.shape[1]
            ):
                g = host[1]
                stack.put("gram", bits, g)
        return g

    @staticmethod
    def _gram_invest(field: Field, stack: Stack, bits):
        """Compute the full gram and keep it, with the stack and (while
        the snapshot is still current) as the field's host copy."""
        R = bits.shape[1]
        g = kernels.pair_gram(bits, list(range(R)))
        if g is not None:
            state = _state(field)
            with state.lock:
                if stack.put("gram", bits, g):
                    state.gram_host = ((stack.versions, R), g)
        return g

    def gram(self, field: Field, stack: Stack, bits, uniq):
        """(gram, pos) answering pair counts for the slot subset ``uniq``:
        the full-row gram kept with the stack (identity positions) or a
        fresh subset gram (enumerated positions); (None, None) when the
        gram path declines entirely.

        A cached gram always matches the snapshot the query reads
        (:meth:`Stack.put`).  The full gram is only computed when the
        subset nearly covers the rows anyway or the snapshot has already
        served GRAM_CACHE_MIN_REUSE subset batches (:meth:`Stack.reused`)."""
        R = bits.shape[1]
        if R <= GRAM_CACHE_MAX_ROWS:
            g = self._gram_at_hand(field, stack, bits)
            if g is not None:
                self.gram_hits += 1
                qprofile.incr("gram_cache_hits")
                return g, {s: s for s in uniq}
            if 2 * len(uniq) >= R or stack.reused("gram", bits):
                g = self._gram_invest(field, stack, bits)
                if g is not None:
                    return g, {s: s for s in uniq}
        g = kernels.pair_gram(bits, uniq)
        if g is None:
            return None, None
        return g, {s: k for k, s in enumerate(uniq)}

    def row_counts(self, stack: Stack, bits) -> np.ndarray:
        """Per-slot row counts ``int64 [R]`` of a snapshot, kept with the
        stack like the gram — repeat unfiltered TopN against an unchanged
        field is then served from host memory with zero device work, the
        reference's ranked-cache role (cache.go).  A cached full gram's
        diagonal is reused instead of launching the count kernel."""
        rc = stack.get("rowcounts", bits)
        if rc is not None:
            self.rowcount_hits += 1
            qprofile.incr("rowcount_cache_hits")
            return rc
        g = stack.get("gram", bits)
        if g is not None:
            rc = np.diag(g).astype(np.int64)
        else:
            rc = kernels.pull(
                kernels.row_counts(bits), "row_counts"
            ).astype(np.int64)
        stack.put("rowcounts", bits, rc)
        return rc

    @staticmethod
    def _cross_at_hand(stack: Stack, bits, partner: Stack, partner_bits):
        """The full cross gram kept with ``stack`` for ``partner``, if it
        was computed from both these snapshots."""
        t = stack.get("crossgram", bits, partner.name)
        if t is None:
            return None
        theirs = t[0]()
        if theirs is None:
            # the partner's snapshot was retired or evicted — drop the
            # slot now rather than letting it linger
            stack.put("crossgram", bits, None, partner.name)
        return t[1] if theirs is partner_bits else None

    def cross_gram(
        self, s1: Stack, bits1, s2: Stack, bits2, sub1: list, sub2: list
    ):
        """Cross-field intersection counts ``int64 [len(sub1), len(sub2)]``
        for two stack snapshots, with the same invest-on-reuse caching as
        :meth:`gram`: once repeat 2-level GroupBys against unchanged
        fields prove reuse, the FULL cross gram is computed once and every
        later combination matrix is sliced from host memory with zero
        device work.  Slots live with the first field's stack, one per
        partner field (so alternating partners don't thrash), and hold the
        partner's snapshot only WEAKLY — a cached gram must never keep a
        retired or budget-evicted device stack alive.  None when the gram
        path declines."""
        R1, R2 = bits1.shape[1], bits2.shape[1]
        if R1 <= GRAM_CACHE_MAX_ROWS and R2 <= GRAM_CACHE_MAX_ROWS:
            g = self._cross_at_hand(s1, bits1, s2, bits2)
            if g is None:
                # the reversed field order may already hold this gram
                # transposed (GroupBy(f, g) then GroupBy(g, f))
                g = self._cross_at_hand(s2, bits2, s1, bits1)
                if g is not None:
                    g = g.T
            if g is not None:
                self.crossgram_hits += 1
                qprofile.incr("crossgram_cache_hits")
                return g[np.ix_(sub1, sub2)]
            nearly_full = 2 * len(sub1) >= R1 and 2 * len(sub2) >= R2
            if nearly_full or s1.reused("crossgram", bits1, s2.name):
                g = kernels.cross_pair_gram(
                    bits1, bits2, list(range(R1)), list(range(R2))
                )
                if g is not None:
                    s1.put(
                        "crossgram", bits1, (weakref.ref(bits2), g),
                        s2.name, cap=CROSS_GRAM_SLOTS,
                    )
                    return g[np.ix_(sub1, sub2)]
        return kernels.cross_pair_gram(bits1, bits2, sub1, sub2)

    def bsi_agg(self, stack: Stack, bits, key: str):
        """An unfiltered BSI aggregate scalar of this snapshot (same
        identity-keyed, write-invalidated scheme as the gram and the row
        counts): repeat unfiltered Sum/Min/Max and range counts against
        an unchanged field are host dictionary hits.  None on a miss."""
        v = stack.get("bsi_agg", bits, key)
        if v is not None:
            self.bsi_agg_hits += 1
            qprofile.incr("bsi_agg_cache_hits")
        return v

    @staticmethod
    def put_bsi_agg(stack: Stack, bits, key: str, value) -> None:
        # range-count keys are open-ended (one per distinct bound), so
        # the store is bounded
        stack.put("bsi_agg", bits, value, key, cap=BSI_AGG_SLOTS)
