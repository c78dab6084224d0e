"""PQL executor (reference: executor.go, 3.2k LoC).

Recursive evaluation of the PQL AST over fragment tensors. Where the
reference runs per-shard map-reduce with goroutine pools and HTTP fan-out
(executor.go:2454-2611), this executor evaluates bitmap algebra directly on
device arrays — per-shard segments combined with fused XLA bitwise kernels
— and leaves multi-device fan-out to pilosa_tpu.parallel (shard_map over a
mesh) and multi-host fan-out to the cluster layer.

Dispatch mirrors the reference table (executor.go:277-342): Sum/Min/Max,
Clear/ClearRow/Store, Count, Set, SetRowAttrs/SetColumnAttrs, TopN, Rows,
GroupBy, Options, and the bitmap calls Row/Range/Difference/Intersect/
Union/Xor/Not/Shift (executor.go:653-680)."""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from datetime import datetime
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from pilosa_tpu import deadline, pql
from pilosa_tpu.core import timequantum
from pilosa_tpu.obs import devledger, qprofile, tracing
from pilosa_tpu.core.field import (
    FIELD_TYPE_BOOL,
    FIELD_TYPE_INT,
    FALSE_ROW_ID,
    TRUE_ROW_ID,
    Field,
)
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core.index import Index
from pilosa_tpu.core.translate import TranslateStore
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.exec import planner as planner_mod
from pilosa_tpu.exec import rescache
from pilosa_tpu.exec import stacks as stacks_mod
from pilosa_tpu.exec.result import (
    FieldRow,
    GroupCount,
    Pair,
    Row,
    RowIdentifiers,
    ValCount,
)
from pilosa_tpu.ops import bitops, bsi
from pilosa_tpu.pql.ast import Call, Condition

# reference executor.go:66 defaultMinThreshold.
DEFAULT_MIN_THRESHOLD = 1

# Sentinel for "not yet computed" result slots in the batch fast path.
_UNSET = object()
# a compiled tree's leaf over a view the field does not have (_tree_stacks)
_ABSENT_VIEW = object()

# the executor's own launches book beside the stack uploads
_DL_STACK = stacks_mod.DL_STACK
# pair-count gram/scan answers: the per-item measured price the flight
# planner's lane chooser weighs against the host latency tier
# (exec/planner.py)
_DL_PAIR = devledger.site("executor.pair_counts")

# the batch lanes, and why one hands an item back to the per-call path:
# an operand's mesh layout, a stack that was not to be had (stacks.refusals
# says why), a lone item of a cold field (the latency tier's by design),
# a shape the lane's kernel does not take, trouble with the item itself
_LANES = (
    "pair_counts", "general", "bsi", "bsi_filtered_counts", "bsi_sums",
    "groupby",
)
_DECLINE_REASONS = ("mesh", "budget", "demand", "shape", "error")

_PAIR_OPS = {
    "Intersect": "intersect",
    "Union": "union",
    "Difference": "difference",
    "Xor": "xor",
}

# Calls that mutate state; the batch fast path must not answer reads that
# appear after one of these in the same query (in-order semantics).
_WRITE_CALLS = {
    "Set",
    "Clear",
    "ClearRow",
    "Store",
    "SetRowAttrs",
    "SetColumnAttrs",
}


# The per-call fall-through's span, one name per call the parser's list
# has; a name outside it (the executor then raises) shares ``Other``.
tracing.register_family(
    "executor.execute", pql.CALL_NAMES + ("Other",),
    "executor lanes", "executor.host_ms_per_flight",
)
_EXECUTE_SPAN = {n: f"executor.execute{n}" for n in pql.CALL_NAMES}


def execute_span(call_name: str) -> str:
    return _EXECUTE_SPAN.get(call_name, "executor.executeOther")


def _pow2(n: int) -> int:
    """Batch sizes pad to powers of two so jit programs are reused
    across drifting batch sizes (shared impl: ops/bitops)."""
    return bitops.pow2_pad_len(n)


def _is_write(call: Call) -> bool:
    """A call writes if it or any descendant writes — Options() (and any
    future wrapper) can wrap a write, so the barrier walks the tree."""
    if call.name in _WRITE_CALLS:
        return True
    return any(_is_write(c) for c in call.children)


class ExecuteError(Exception):
    pass


class TooManyWritesError(ExecuteError):
    """reference pilosa.go:59 ErrTooManyWrites."""


class IndexNotFoundError(ExecuteError):
    pass


class FieldNotFoundError(ExecuteError):
    pass


class Executor:
    # reference server/config.go:160 MaxWritesPerRequest default
    DEFAULT_MAX_WRITES_PER_REQUEST = 5000

    def __init__(
        self,
        holder: Holder,
        translator: TranslateStore | None = None,
        max_writes_per_request: int | None = None,
        rescache_entries: int = 512,
        rescache_promote_hits: int = 3,
        rescache_demote_deltas: int = 64,
        planner_enabled: bool = True,
    ):
        self.holder = holder
        self.translator = translator or TranslateStore()
        # flight-level query planner (exec/planner.py, docs/serving.md
        # "Flight planning"): cross-query CSE + cost-based reordering +
        # measured lane choice, applied per execute_batch shard group
        self.planner = planner_mod.FlightPlanner(
            self, enabled=planner_enabled
        )
        # semantic result cache (exec/rescache.py, docs/caching.md):
        # translated read calls keyed by canonical AST + fragment version
        # vector, probed ahead of the batch fast paths; 0 entries
        # disables it
        self.rescache = rescache.ResultCache(
            entries=rescache_entries,
            promote_hits=rescache_promote_hits,
            demote_deltas=rescache_demote_deltas,
            stats_fn=lambda: holder.stats,
        )
        # mutating-call cap per request (reference executor.go:55,138 +
        # config max-writes-per-request); 0 disables
        self.max_writes_per_request = (
            self.DEFAULT_MAX_WRITES_PER_REQUEST
            if max_writes_per_request is None
            else max_writes_per_request
        )
        # the device form of the holder's fields, what is cached against
        # it, and this executor's count of both (exec/stacks.py)
        self.stacks = stacks_mod.Stacks()
        # stacked-BSI launches (tests assert O(1) dispatch per BSI query)
        self.bsi_stack_launches = 0
        # flight items a batch lane handed back to the per-call path, by
        # lane and reason: the slot is re-executed — and an error
        # re-raised — in the owning query's demux scope, so this counts
        # fallbacks, not lost queries.  Every key is there from the start
        # (/debug/vars readers take deltas)
        self.lane_declines = {
            lane: dict.fromkeys(_DECLINE_REASONS, 0) for lane in _LANES
        }
        # the GroupBy lane (_groupby_lane): calls it took, pulls it made,
        # the levels enqueued and not yet pulled summed over those pulls
        # (the pulled one included: 1 a pull is the per-call order), and
        # levels the byte bound held back
        self.groupby_lane = dict.fromkeys(
            ("calls", "pulls", "inflight_sum", "budget_waits"), 0
        )
        # the Sum lane (_batch_bsi_sums): filtered Sums whose filter was
        # built on the device, in the Sum's own program; filtered Sums it
        # left to the per-call path, which builds theirs on the host; and
        # its launches
        self.sum_lane = dict.fromkeys(
            ("device_filters", "host_filters", "launches"), 0
        )

    # ------------------------------------------------------------------ API

    def execute(
        self,
        index_name: str,
        query: str | pql.Query,
        shards: list[int] | None = None,
    ) -> list[Any]:
        """reference executor.go:116 Execute: translate -> execute ->
        attach attrs -> translate results."""
        idx = self.holder.index(index_name)
        if idx is None:
            raise IndexNotFoundError(f"index not found: {index_name}")
        q = pql.parse(query) if isinstance(query, str) else query
        if (
            self.max_writes_per_request > 0
            and len(q.write_calls()) > self.max_writes_per_request
        ):
            # reference executor.go:138 + pilosa.go:59 ErrTooManyWrites
            raise TooManyWritesError("too many write commands")
        # span per query (reference executor.go:117 "Executor.Execute")
        with tracing.start_span("executor.Execute").set_tag("index", index_name):
            calls = [c.clone() for c in q.calls]
            for call in calls:
                self._translate_call(idx, call)
            results: list[Any] = [_UNSET] * len(calls)
            # Serving-mode fast paths: many Count(op(Row,Row)) calls in
            # one query collapse into a single gram launch, and arbitrary
            # Row/op/Not trees compile into one traced program per AST
            # shape (exec/astbatch.py).  Only calls BEFORE the first
            # write are eligible: they observe exactly the pre-loop
            # state they would see executing in order.
            first_write = next(
                (i for i, c in enumerate(calls) if _is_write(c)), len(calls)
            )
            # Semantic cache probe ahead of kernel dispatch: a repeated
            # read whose fragment version vector is unchanged skips the
            # batch passes entirely (exec/rescache.py).
            tokens: list[Any] = [None] * len(calls)
            for i, call in enumerate(calls[:first_write]):
                res, tokens[i] = self.rescache.lookup(idx, call, shards)
                if res is not rescache.MISS:
                    results[i] = res
            self._batch_pair_counts(idx, calls[:first_write], shards, results)
            self._batch_general(idx, calls[:first_write], shards, results)
            with self._flight_lanes(
                idx, calls[:first_write], shards, results
            ) as late:
                self._batch_bsi(
                    idx, calls[:first_write], shards, results, late
                )
            for i, call in enumerate(calls):
                if results[i] is _UNSET:
                    with tracing.start_span(execute_span(call.name)):
                        results[i] = self._execute_call(idx, call, shards)
            for i, call in enumerate(calls[:first_write]):
                if tokens[i] is not None:
                    self.rescache.store(
                        tokens[i],
                        results[i],
                        recompute=self._maintained_recompute(idx, call, shards),
                    )
            return [
                self._translate_result(idx, c, r) for c, r in zip(q.calls, results)
            ]

    def execute_batch(
        self,
        index_name: str,
        queries: list[tuple[str | pql.Query, list[int] | None]],
    ) -> list[Any]:
        """Cross-request micro-batch entry point (the continuous-batching
        serving plane, server/batcher.py): execute several independent
        read-only queries as ONE pass through the batched fast paths, so
        concurrent HTTP requests share gram/AST-batch device launches
        instead of each paying its own host→device round trip.

        ``queries`` is ``[(query, shards), ...]``.  Returns one slot per
        query: the query's result list, or the exception it raised —
        per-query isolation, one malformed query must not fail the
        flight it shares a window with.  A query that turns out to
        carry writes falls back to the ordinary in-order :meth:`execute`
        path (the batcher filters writes out already; this is the
        defensive second fence).  Queries with differing shard
        restrictions batch within their shard group."""
        idx = self.holder.index(index_name)
        if idx is None:
            err = IndexNotFoundError(f"index not found: {index_name}")
            return [err for _ in queries]
        n = len(queries)
        out: list[Any] = [None] * n
        parsed: list[pql.Query | None] = [None] * n
        cloned: list[list[Call] | None] = [None] * n
        with tracing.start_span("executor.ExecuteBatch").set_tag(
            "index", index_name
        ).set_tag("queries", n):
            # Per-query translate, grouped by shard restriction so the
            # flat batch passes see one consistent shard list.
            groups: dict[tuple[int, ...] | None, list[int]] = {}
            for qi, (query, shards) in enumerate(queries):
                try:
                    q = pql.parse(query) if isinstance(query, str) else query
                    if q.write_calls():
                        out[qi] = self.execute(index_name, q, shards=shards)
                        continue
                    parsed[qi] = q
                    calls = [c.clone() for c in q.calls]
                    for call in calls:
                        self._translate_call(idx, call)
                    cloned[qi] = calls
                    key = tuple(sorted(shards)) if shards else None
                    groups.setdefault(key, []).append(qi)
                except Exception as e:
                    out[qi] = e
            for key, qis in groups.items():
                shards = list(key) if key is not None else None
                flat_calls = [c for qi in qis for c in cloned[qi]]
                flat_results: list[Any] = [_UNSET] * len(flat_calls)
                # cache probe before the flat batch passes: flight
                # members served here never ride the device launch
                flat_tokens: list[Any] = [None] * len(flat_calls)
                for fi, call in enumerate(flat_calls):
                    res, flat_tokens[fi] = self.rescache.lookup(
                        idx, call, shards
                    )
                    if res is not rescache.MISS:
                        flat_results[fi] = res
                # flight planning AFTER the cache probe (tokens and keys
                # are captured; grafts/reorders cannot shift identity)
                # and BEFORE the batch passes (grafted trees must fall
                # to host segment algebra, which is the sharing win)
                with tracing.start_span("planner.plan").set_tag(
                    "n", len(flat_calls)
                ):
                    self.planner.plan_group(
                        idx, flat_calls, shards, flat_results, _UNSET
                    )
                self._batch_pair_counts(idx, flat_calls, shards, flat_results)
                self._batch_general(idx, flat_calls, shards, flat_results)
                with self._flight_lanes(
                    idx, flat_calls, shards, flat_results
                ) as late:
                    self._batch_bsi(
                        idx, flat_calls, shards, flat_results, late
                    )
                pos = 0
                for qi in qis:
                    calls = cloned[qi]
                    res = flat_results[pos:pos + len(calls)]
                    toks = flat_tokens[pos:pos + len(calls)]
                    pos += len(calls)
                    try:
                        for ci, call in enumerate(calls):
                            if res[ci] is _UNSET:
                                with tracing.start_span(
                                    execute_span(call.name)
                                ):
                                    res[ci] = self._execute_call(
                                        idx, call, shards
                                    )
                        with tracing.start_span("executor.demux").set_tag(
                            "n", len(calls)
                        ):
                            for ci, call in enumerate(calls):
                                if toks[ci] is not None:
                                    self.rescache.store(
                                        toks[ci],
                                        res[ci],
                                        recompute=self._maintained_recompute(
                                            idx, call, shards
                                        ),
                                    )
                            out[qi] = [
                                self._translate_result(idx, c, r)
                                for c, r in zip(parsed[qi].calls, res)
                            ]
                    except Exception as e:
                        out[qi] = e
        return out

    def rescache_probe(
        self,
        index_name: str,
        q: pql.Query,
        shards: list[int] | None = None,
    ) -> list[Any] | None:
        """All-or-nothing semantic cache probe for a whole parsed query:
        the batcher calls this at submit time so a flight member whose
        every call hits demuxes instantly instead of riding the device
        launch (server/batcher.py).  Returns the translated result list,
        or None when any call misses (the query then takes the normal
        path — the probe counts no miss twice since lookup tokens are
        discarded).

        A request of one call whose index and fields hold no keys gets
        the cached object itself (``rescache.Served``): translation
        would write nothing, so the hit neither copies nor walks its
        answer, and the listener may send the bytes an earlier hit left
        with the entry.  The list is read-only by contract.  A request
        of several calls, or one that collects a profile, takes the
        copying path below."""
        idx = self.holder.index(index_name)
        if idx is None or not q.calls or q.write_calls():
            return None
        try:
            if len(q.calls) == 1 and not qprofile.profiling():
                call = q.calls[0].clone()
                self._translate_call(idx, call)
                hit = self.rescache.lookup_shared(idx, call, shards)
                if hit is rescache.MISS:
                    return None
                if not isinstance(hit, rescache.Served):
                    self._translate_result(idx, q.calls[0], hit[0])
                return hit
            results = []
            for orig in q.calls:
                call = orig.clone()
                self._translate_call(idx, call)
                res, _tok = self.rescache.lookup(idx, call, shards)
                if res is rescache.MISS:
                    return None
                results.append(res)
            return [
                self._translate_result(idx, c, r)
                for c, r in zip(q.calls, results)
            ]
        except Exception:
            return None

    def rescache_degraded(
        self,
        index_name: str,
        q: pql.Query,
        shards: list[int] | None = None,
    ) -> list[Any] | None:
        """Degraded-tier variant of :meth:`rescache_probe`: all-or-
        nothing over LAST-KNOWN cache entries with the version check
        waived (rescache.lookup_stale).  The QoS governor routes a
        pressure-staged tenant's TopN/GroupBy here (server/qos.py);
        the caller marks the response as degraded.  Returns None when
        any call has no last-known entry — the query then runs for
        real at its reduced weight."""
        idx = self.holder.index(index_name)
        if idx is None or not q.calls or q.write_calls():
            return None
        try:
            results = []
            for orig in q.calls:
                call = orig.clone()
                self._translate_call(idx, call)
                res = self.rescache.lookup_stale(idx, call, shards)
                if res is rescache.MISS:
                    return None
                results.append(res)
            return [
                self._translate_result(idx, c, r)
                for c, r in zip(q.calls, results)
            ]
        except Exception:
            return None

    def cached_execute_call(
        self, idx: Index, call: Call, shards: list[int] | None
    ) -> Any:
        """One translated call through the semantic cache — the
        distributed layer's per-owner partial path (cluster/dist.py):
        local and mesh-facade partials cache under the owner's version
        subvector, so a reduce over partials stays correct across
        resize epochs (fragment epoch is part of the vector)."""
        res, token = self.rescache.lookup(idx, call, shards)
        if res is not rescache.MISS:
            return res
        out = self._execute_call(idx, call, shards)
        if token is not None:
            self.rescache.store(
                token, out,
                recompute=self._maintained_recompute(idx, call, shards),
            )
        return out

    def _maintained_recompute(
        self, idx: Index, call: Call, shards: list[int] | None
    ):
        """The promotion closure for hot TopN/GroupBy entries: re-derive
        the result from the incrementally maintained per-fragment row
        counts (``Fragment._counts``, carried through point writes and
        imports in the same group-commit) instead of invalidating.
        Unfiltered TopN re-merges the maintained counts host-side — no
        device dispatch; GroupBy re-runs its aggregation over the same
        maintained state.  Other call shapes don't promote (None)."""
        if call.name == "TopN" and not call.children:
            pass
        elif call.name == "GroupBy" and "filter" not in call.args:
            pass
        else:
            return None
        frozen = call.clone()

        def recompute():
            return self._execute_call(idx, frozen.clone(), shards)

        return recompute

    def _after_write(self, idx: Index, call: Call, result: Any) -> Any:
        self._note_write_call(idx, call)
        return result

    def _note_write_call(self, idx: Index, call: Call) -> None:
        """Eager precise invalidation after a write call executed: drop
        only the cache entries reading the written field.  Column-attr
        writes have no field — they drop the index's entries (attrs are
        outside the fragment version space, so the version vector can't
        catch them)."""
        name = call.name
        if name == "SetColumnAttrs":
            self.rescache.note_write(idx.name, None)
            return
        if name == "SetRowAttrs":
            fname = call.args.get("_field")
        else:
            fname = call.field_arg()
        if isinstance(fname, str):
            self.rescache.note_write(idx.name, fname)
            if idx.track_existence and name in ("Set", "Store"):
                self.rescache.note_write(idx.name, "_exists")
        else:
            self.rescache.note_write(idx.name, None)

    # ----------------------------------------------- batched Count fast path

    def _match_pair_count(self, idx: Index, call: Call):
        """(field_name, op, row_a, row_b) when ``call`` is a batchable
        ``Count(op(Row(f=a), Row(f=b)))`` over one set-like field; None
        otherwise."""
        if call.name != "Count" or len(call.children) != 1 or call.args:
            return None
        child = call.children[0]
        op = _PAIR_OPS.get(child.name)
        if op is None or len(child.children) != 2 or child.args:
            return None
        fname = None
        rows: list[int] = []
        for rc in child.children:
            if rc.name != "Row" or rc.children:
                return None
            f = rc.field_arg()
            if f is None or set(rc.args) != {f}:
                return None
            v = rc.args.get(f)
            if not isinstance(v, int) or isinstance(v, bool):
                return None
            if fname is None:
                fname = f
            elif fname != f:
                return None
            rows.append(v)
        field = idx.field(fname)
        if field is None or field.field_type == FIELD_TYPE_INT:
            return None
        if field.view(VIEW_STANDARD) is None:
            return None
        return fname, op, rows[0], rows[1]

    def _lane_decline(self, lane: str, reason: str, n: int = 1) -> None:
        """``n`` items of a flight go back to the per-call path."""
        self.lane_declines[lane][reason] += n

    def _count_stat(self, idx: Index, call_name: str = "Count") -> None:
        """query_total stat for a batch-answered call (the per-call path
        emits this in _execute_call; batch paths must match)."""
        self.holder.stats.count_with_tags(
            "query_total", 1, 1.0, (f"index:{idx.name}", f"call:{call_name}")
        )

    # lone Count(op(Row,Row)) queries against one field seen before the
    # stack+gram investment is judged worthwhile for singles (the warm-up
    # the reference's ranked cache pays on its first TopN, cache.go)
    _PAIR_SINGLE_WARM = 4

    def _pair_single_ready(self, field: Field, shard_list: list[int]) -> bool:
        """Whether a LONE pair-count should take the gram path. True when
        a serving stack is already live (answering from it beats the
        per-fragment path, and repeat singles then install + hit the
        cached host gram: zero device work per query) or when repeat
        singles against this field prove reuse.  Once the cost ledger
        has priced both lanes, the measured comparison replaces the
        warm-up counter (exec/planner.py lane choice)."""
        if self.stacks.cached(field, shard_list):
            return True
        return self.planner.choose_lane(
            "pair_count",
            stacks_mod.note_single(field, "pair") >= self._PAIR_SINGLE_WARM,
        )

    @stacks_mod.reading()
    def _batch_pair_counts(
        self, idx: Index, calls: list[Call], shards: list[int] | None,
        results: list[Any],
    ) -> None:
        """Answer every batchable Count(op(Row,Row)) call in ``calls``
        (the caller has already truncated at the first write barrier)
        with one gram launch per field — the serving-mode shape where the
        reference would run one goroutine map-reduce per query
        (executor.go:2454-2518).

        A field engages only when >= 2 of its Counts batch (the stack
        build is full-field; version-keyed caching makes it pay off on
        read-heavy serving workloads, while write-interleaved workloads
        fall through to the per-call path)."""
        from pilosa_tpu.ops import kernels

        by_field: dict[str, list[tuple[int, str, int, int]]] = {}
        for i, call in enumerate(calls):
            m = self._match_pair_count(idx, call)
            if m is not None:
                fname, op, ra, rb = m
                by_field.setdefault(fname, []).append((i, op, ra, rb))
        shard_list = None
        _count_stat = lambda: self._count_stat(idx)

        for fname, items in by_field.items():
            field = idx.field(fname)
            if shard_list is None:
                shard_list = self._shards_for(idx, shards)
            if len(items) < 2 and not self._pair_single_ready(
                field, shard_list
            ):
                self._lane_decline("pair_counts", "demand")
                continue
            stack = self.stacks.get(field, shard_list)
            if stack is None:
                self._lane_decline("pair_counts", "budget", len(items))
                if len(items) < 2:
                    # over-budget field: restart the warm-up so singles
                    # don't pay a declined build attempt on every query
                    stacks_mod.reset_single(field, "pair")
                continue
            slot_of, bits = stack.slot_of, stack.bits
            launch: list[tuple[int, str, int, int]] = []
            for i, op, ra, rb in items:
                sa, sb = slot_of.get(ra), slot_of.get(rb)
                if sa is None or sb is None:
                    # Intersect with an absent row is provably 0; other
                    # ops (union/difference/xor) need the present side's
                    # count, so they take the normal path.
                    if op == "intersect":
                        results[i] = 0
                        _count_stat()
                    else:
                        self._lane_decline("pair_counts", "shape")
                    continue
                launch.append((i, op, sa, sb))
            if not launch:
                continue
            # One gram launch answers ALL ops in the batch — each pair op
            # is a formula over gram entries (|a|b| = Gaa+Gbb-Gab, ...),
            # so mixed Intersect/Union/Difference/Xor Counts share one
            # index scan on the MXU (kernels.pair_gram).
            uniq = sorted({s for _, _, sa, sb in launch for s in (sa, sb)})
            with tracing.start_span("executor.batchPairCount").set_tag(
                "field", fname
            ).set_tag("n", len(launch)), _DL_PAIR.launch(
                sig=f"gram n{len(launch)}", n=len(launch)
            ):
                gram, pos = self.stacks.gram(field, stack, bits, uniq)
                if gram is not None:
                    with tracing.start_span("executor.demux").set_tag(
                        "n", len(launch)
                    ):
                        pa = np.array([pos[sa] for _, _, sa, _ in launch])
                        pb = np.array([pos[sb] for _, _, _, sb in launch])
                        for op in {op for _, op, _, _ in launch}:
                            sel = [
                                j for j, it in enumerate(launch)
                                if it[1] == op
                            ]
                            counts = kernels.pair_counts_from_gram(
                                gram, pa[sel], pb[sel], op
                            )
                            for c, j in zip(counts, sel):
                                results[launch[j][0]] = int(c)
                                _count_stat()
                    continue
                # gram declined (too many distinct rows): scan kernels,
                # one launch per op, padded to powers of two for program
                # reuse.  Local stacks return [B, S] per-shard partials
                # (summed host-side in int64 so totals past 2^31 stay
                # exact); process-spanning stacks return replicated
                # int64[B] in-program psum totals (kernels.py r05).
                if not kernels.row_counts_supported(bits):
                    # spanning mesh too large even for the chunked psum
                    # — leave these results unset so the per-call
                    # per-fragment path answers them
                    self._lane_decline("pair_counts", "mesh", len(launch))
                    continue
                by_op: dict[str, list[tuple[int, int, int]]] = {}
                for i, op, sa, sb in launch:
                    by_op.setdefault(op, []).append((i, sa, sb))
                for op, olaunch in by_op.items():
                    B = _pow2(len(olaunch))
                    if B > len(olaunch):
                        kernels.note_pad(
                            "pair_count",
                            B * bits.shape[0] * 4,
                            len(olaunch) * bits.shape[0] * 4,
                        )
                    ras = np.zeros(B, dtype=np.int32)
                    rbs = np.zeros(B, dtype=np.int32)
                    for j, (_, sa, sb) in enumerate(olaunch):
                        ras[j], rbs[j] = sa, sb
                    partials = kernels.pull(
                        kernels.pair_count_batched(
                            bits, kernels.h2d(ras), kernels.h2d(rbs), op=op
                        ),
                        "pair_count",
                    ).astype(np.int64)
                    with tracing.start_span("executor.demux").set_tag(
                        "n", len(olaunch)
                    ):
                        counts = (
                            partials if partials.ndim == 1
                            else partials.sum(axis=1)
                        )
                        for j, (i, _, _) in enumerate(olaunch):
                            results[i] = int(counts[j])
                            _count_stat()

    # ------------------------------------------ general AST one-launch path

    def _stack_on_demand(
        self, field: Field, shard_list: list[int], view_name: str,
        demand: int,
    ):
        """The view's stack for a batch lane's leaf (an int field's BSI
        view: its raw BSI stack), or None when it declines.  A live
        stack serves for free; a cold one is built only when >= 2 calls
        of the flight read it (stack builds are full-field uploads), a
        heuristic that stands until the ledger prices the batch-vs-solo
        lanes."""
        raw = field.is_bsi()
        live = (
            self.stacks.bsi_cached(field, shard_list) if raw
            else self.stacks.cached(field, shard_list, view_name)
        )
        if live or self.planner.choose_lane("tree_count", demand >= 2):
            return (
                self.stacks.bsi(field, shard_list) if raw
                else self.stacks.get(field, shard_list, view_name)
            )
        self.stacks.refusals["demand"] += 1
        return None

    def _tree_stacks(
        self, idx: Index, pairs, shard_list: list[int],
        demand: dict[tuple[str, str], int], memo: dict,
        allow_spanning: bool,
    ):
        """(stacks tuple, slot_of per (field, view)) for the ``pairs`` a
        compiled tree reads, or the reason (of _DECLINE_REASONS) when any
        leaf declines (cold + under-demanded, or over budget).  ``memo``
        is the flight's: (field, view) -> (slot_of, bits) of its stack |
        the reason it declined | _ABSENT_VIEW (no such view: an all-zero
        leaf, e.g. an empty period of a time-range cover).
        ``allow_spanning``: count programs reduce in-program on a
        process-spanning mesh (astbatch._compiled_spanning), but what
        comes back per shard or per device (a bitmap program's [S, W]
        words, a Sum's accumulators) is not addressable across
        processes, so those decline."""
        from pilosa_tpu.ops import kernels

        out: list[Any] = []
        slot_maps = {}
        for pair in pairs:
            fname, vname = pair
            if pair not in memo:
                field = idx.field(fname)  # includes _exists
                if field is None:
                    memo[pair] = "shape"
                elif field.view(vname) is None:
                    # a range leaf reads its stack's own rows: no stand-in
                    memo[pair] = "shape" if field.is_bsi() else _ABSENT_VIEW
                else:
                    stack = self._stack_on_demand(
                        field, shard_list, vname, demand.get(pair, 0)
                    )
                    memo[pair] = (
                        "budget" if stack is None
                        else (stack.slot_of, stack.bits)
                    )
            entry = memo[pair]
            if isinstance(entry, str):
                return entry
            if entry is _ABSENT_VIEW:
                slot_maps[pair] = {}
                out.append(None)  # placeholder filled below
            else:
                slot_maps[pair] = entry[0]
                out.append(entry[1])
        # absent views still need a stack-shaped input for their
        # argument position: reuse any real stack — every such
        # leaf's slot is -1, which masks the gather to zero words
        real = next((a for a in out if a is not None), None)
        if real is None:
            return "shape"  # every leaf view absent
        if not allow_spanning and kernels.stack_spans_processes(real):
            return "mesh"
        return tuple(a if a is not None else real for a in out), slot_maps

    @stacks_mod.reading()
    def _batch_general(
        self, idx: Index, calls: list[Call], shards: list[int] | None,
        results: list[Any],
    ) -> None:
        """Compile remaining batchable reads — any tree of
        Row/Intersect/Union/Difference/Xor/Not, under Count or as a
        bitmap result — into one traced launch per AST shape over the
        field stacks (SURVEY §7's "one XLA program per query shape";
        reference semantics executor.go:653-680).

        The caller truncates ``calls`` at the first write barrier.  A
        call engages only when every leaf field either already has a
        live stack or is demanded by >= 2 batchable calls in this query
        (stack builds are full-field uploads; they must amortize)."""
        from pilosa_tpu.exec import astbatch

        # launch groups key on (canonical sig, actual stack pairs): the
        # COMPILED program is shared across groups with the same shape
        # (astbatch.compiled caches on sig alone — a rolling time window
        # reuses one program), but each group launches with its own
        # stacks
        count_groups: dict[tuple, list[tuple[int, list]]] = {}
        bitmap_items: list[tuple[int, tuple, tuple, list]] = []
        demand: dict[tuple[str, str], int] = {}
        for i, call in enumerate(calls):
            if results[i] is not _UNSET:
                continue
            leaves: list[tuple[str, str, int]] = []
            pairs: list[tuple[str, str]] = []
            sig = astbatch.match_count(idx, call, leaves, pairs)
            if sig is not None:
                count_groups.setdefault((sig, tuple(pairs)), []).append(
                    (i, leaves)
                )
            elif call.name in ("Intersect", "Union", "Difference", "Xor", "Not"):
                leaves, pairs = [], []
                sig = astbatch.match_tree(idx, call, leaves, pairs)
                if sig is None:
                    continue
                bitmap_items.append((i, sig, tuple(pairs), leaves))
            else:
                continue
            for pair in pairs:
                demand[pair] = demand.get(pair, 0) + 1
        if not count_groups and not bitmap_items:
            return
        shard_list = self._shards_for(idx, shards)

        # the flight's stacks by (field, view): _tree_stacks' memo
        stacks_by_view: dict[tuple[str, str], Any] = {}

        def _slots_of(leaves, slot_maps) -> np.ndarray:
            # absent rows -> slot -1 (masked to zero words in the leaf)
            return np.array(
                [slot_maps[(f, vn)].get(r, -1) for f, vn, r in leaves],
                np.int32,
            )

        for (sig, pairs), items in count_groups.items():
            st = self._tree_stacks(
                idx, pairs, shard_list, demand, stacks_by_view,
                allow_spanning=True,
            )
            if isinstance(st, str):
                self._lane_decline("general", st, len(items))
                continue
            stacks, slot_maps = st
            # same availability contract as every spanning lane: when
            # even a single-shard psum slice could overflow int32,
            # DECLINE to the per-call path instead of letting
            # run_count_batch's ValueError reach the client
            from pilosa_tpu.ops import kernels as _kk

            if not _kk.row_counts_supported(stacks[0]):
                self._lane_decline("general", "mesh", len(items))
                continue
            B = _pow2(len(items))
            slots = np.full((B, len(items[0][1])), -1, np.int32)
            for j, (_, leaves) in enumerate(items):
                slots[j] = _slots_of(leaves, slot_maps)
            with tracing.start_span("executor.batchCountTree").set_tag(
                "n", len(items)
            ):
                totals = astbatch.run_count_batch(sig, stacks, slots)
                with tracing.start_span("executor.demux").set_tag(
                    "n", len(items)
                ):
                    for j, (i, _) in enumerate(items):
                        results[i] = int(totals[j])
                        self._count_stat(idx)

        for i, sig, pairs, leaves in bitmap_items:
            st = self._tree_stacks(
                idx, pairs, shard_list, demand, stacks_by_view,
                allow_spanning=False,
            )
            if isinstance(st, str):
                self._lane_decline("general", st)
                continue
            stacks, slot_maps = st
            with tracing.start_span("executor.batchBitmapTree"):
                dev = astbatch.run_bitmap(
                    sig, stacks, _slots_of(leaves, slot_maps)
                )
                if getattr(dev, "sharding", None) is not None and len(
                    getattr(dev.sharding, "device_set", ())
                ) > 1:
                    # mesh-sharded result: one host pull, numpy segments
                    # (device slices would pin segments to different
                    # chips and later segment algebra would mix
                    # placements)
                    from pilosa_tpu.ops import kernels

                    dev = kernels.pull(dev, "ast_bitmap")
                with tracing.start_span("executor.demux").set_tag("n", 1):
                    segments = {
                        s: dev[si] for si, s in stacks_mod.positions(
                            shard_list, stacks[0]
                        )
                    }
                    results[i] = Row(segments, n_words=idx.n_words)
                    self._count_stat(idx, calls[i].name)

    # ------------------------------------------------------- key translation

    def _field_of_call(self, idx: Index, call: Call) -> Field | None:
        fname = call.args.get("_field") or call.field_arg()
        if fname is None:
            return None
        return idx.field(fname)

    def _translate_call(self, idx: Index, call: Call) -> None:
        """keys -> ids in place. Mirrors the reference's per-call-name arg
        dispatch (executor.go:2625-2712 translateCall): each call shape
        names which args hold column keys vs row keys."""
        name = call.name
        if name == "GroupBy":
            self._translate_groupby(idx, call)
            return
        if name in ("Set", "Clear", "Row", "Range", "SetColumnAttrs", "ClearRow"):
            col_key = "_col"
            field_name = call.field_arg()
            row_key = field_name
        elif name == "SetRowAttrs":
            col_key = None
            row_key = "_row"
            field_name = call.args.get("_field")
        elif name == "Rows":
            field_name = call.args.get("_field")
            row_key = "previous"
            col_key = "column"
        else:
            col_key = "col"
            field_name = call.args.get("field")
            row_key = "row"

        # Translate column key (reference executor.go:2648-2664).
        if col_key is not None:
            col = call.args.get(col_key)
            if idx.keys:
                if col is not None and not isinstance(col, str):
                    raise ExecuteError(
                        "column value must be a string when index 'keys' option enabled"
                    )
                if isinstance(col, str):
                    call.args[col_key] = self.translator.translate_key(
                        idx.name, "", col
                    )
            elif isinstance(col, str):
                raise ExecuteError(
                    "string 'col' value not allowed unless index 'keys' option enabled"
                )

        # Translate row key (reference executor.go:2666-2712).
        if field_name:
            field = idx.field(field_name)
            if field is not None and row_key is not None:
                v = call.args.get(row_key)
                if field.field_type == FIELD_TYPE_BOOL and isinstance(v, bool):
                    call.args[row_key] = TRUE_ROW_ID if v else FALSE_ROW_ID
                elif field.keys:
                    if v is not None and not isinstance(v, str):
                        raise ExecuteError(
                            "row value must be a string when field 'keys' option enabled"
                        )
                    if isinstance(v, str):
                        call.args[row_key] = self.translator.translate_key(
                            idx.name, field_name, v
                        )
                elif isinstance(v, str):
                    raise ExecuteError(
                        "string 'row' value not allowed unless field 'keys' option enabled"
                    )

        for child in call.children:
            self._translate_call(idx, child)
        filt = call.args.get("filter")
        if isinstance(filt, Call):
            self._translate_call(idx, filt)

    def _translate_groupby(self, idx: Index, call: Call) -> None:
        """The `previous` paging list holds one row key/id per child field
        (reference executor.go:2718-2748 translateGroupByCall)."""
        for child in call.children:
            self._translate_call(idx, child)
        filt = call.args.get("filter")
        if isinstance(filt, Call):
            self._translate_call(idx, filt)
        previous = call.args.get("previous")
        if previous is None:
            return
        if not isinstance(previous, list):
            raise ExecuteError("'previous' argument must be a list")
        if len(previous) != len(call.children):
            raise ExecuteError(
                "'previous' argument must have a value for each GroupBy field"
            )
        for i, (child, prev) in enumerate(zip(call.children, previous)):
            fname = child.args.get("_field")
            field = idx.field(fname) if fname else None
            if field is None:
                continue
            if field.field_type == FIELD_TYPE_BOOL and isinstance(prev, bool):
                previous[i] = TRUE_ROW_ID if prev else FALSE_ROW_ID
            elif isinstance(prev, str):
                if not field.keys:
                    raise ExecuteError(
                        f"prev value must be a uint64 for field {fname!r}"
                    )
                previous[i] = self.translator.translate_key(idx.name, fname, prev)

    def _translate_result(self, idx: Index, call: Call, result: Any) -> Any:
        """ids -> keys on results (reference executor.go:2783-2907)."""
        if isinstance(result, Row) and idx.keys:
            result.keys = self.translator.translate_ids(
                idx.name, "", [int(c) for c in result.columns()]
            )
        elif isinstance(result, list) and result and isinstance(result[0], Pair):
            field = self._field_of_call(idx, call)
            if field is not None and field.keys:
                keys = self.translator.translate_ids(
                    idx.name, field.name, [p.id for p in result]
                )
                for p, k in zip(result, keys):
                    p.key = k
        elif isinstance(result, Pair):
            field = self._field_of_call(idx, call)
            if field is not None and field.keys:
                result.key = self.translator.translate_id(
                    idx.name, field.name, result.id
                )
        elif isinstance(result, RowIdentifiers):
            field = self._field_of_call(idx, call)
            if field is not None and field.keys:
                result.keys = self.translator.translate_ids(
                    idx.name, field.name, result.rows
                )
        elif isinstance(result, list) and result and isinstance(result[0], GroupCount):
            for gc in result:
                for fr in gc.group:
                    field = idx.field(fr.field)
                    if field is not None and field.keys:
                        fr.row_key = self.translator.translate_id(
                            idx.name, fr.field, fr.row_id
                        )
        return result

    # ------------------------------------------------------------- dispatch

    def _shards_for(self, idx: Index, shards: list[int] | None) -> list[int]:
        if shards is not None:
            return sorted(shards)
        return sorted(idx.available_shards())

    @stacks_mod.reading()
    def _execute_call(self, idx: Index, call: Call, shards: list[int] | None) -> Any:
        name = call.name
        # Stop before starting a shard scan the caller will never wait
        # for — the deadline contextvar follows forwarded sub-queries
        # here via the X-Pilosa-Deadline header (pilosa_tpu/deadline.py).
        deadline.check(f"executing {name} on {idx.name!r}")
        # Per-call-type query counts (reference executor.go:298-339).
        self.holder.stats.count_with_tags(
            "query_total", 1, 1.0, (f"index:{idx.name}", f"call:{name}")
        )
        if name == "Sum":
            return self._execute_sum(idx, call, shards)
        if name == "Min":
            return self._execute_min_max(idx, call, shards, maximal=False)
        if name == "Max":
            return self._execute_min_max(idx, call, shards, maximal=True)
        if name == "MinRow":
            return self._execute_min_max_row(idx, call, shards, maximal=False)
        if name == "MaxRow":
            return self._execute_min_max_row(idx, call, shards, maximal=True)
        if name == "Clear":
            return self._after_write(idx, call, self._execute_clear(idx, call))
        if name == "ClearRow":
            return self._after_write(
                idx, call, self._execute_clear_row(idx, call, shards)
            )
        if name == "Store":
            return self._after_write(
                idx, call, self._execute_store(idx, call, shards)
            )
        if name == "Count":
            return self._execute_count(idx, call, shards)
        if name == "Set":
            return self._after_write(idx, call, self._execute_set(idx, call))
        if name == "SetRowAttrs":
            return self._after_write(
                idx, call, self._execute_set_row_attrs(idx, call)
            )
        if name == "SetColumnAttrs":
            return self._after_write(
                idx, call, self._execute_set_column_attrs(idx, call)
            )
        if name == "TopN":
            return self._execute_topn(idx, call, shards)
        if name == "Rows":
            return self._execute_rows(idx, call, shards)
        if name == "GroupBy":
            return self._execute_groupby(idx, call, shards)
        if name == "Options":
            return self._execute_options(idx, call, shards)
        # bitmap calls
        return self._execute_bitmap_call(idx, call, shards)

    # --------------------------------------------------------- bitmap calls

    def _execute_bitmap_call(self, idx: Index, call: Call, shards: list[int] | None) -> Row:
        """reference executor.go:653-680 executeBitmapCallShard + attr
        attach (executor.go:235-275)."""
        row = self._bitmap_call(idx, call, self._shards_for(idx, shards))
        # attach row attrs for a plain Row(f=<id>) (reference
        # executor.go:244-263)
        if call.name in ("Row", "Range"):
            fname = call.field_arg()
            if fname is not None:
                v = call.args.get(fname)
                field = idx.field(fname)
                if field is not None and isinstance(v, int) and not isinstance(v, bool):
                    row.attrs = field.row_attrs.attrs(v)
        return row

    # ------------------------------------------------- batched GroupBy lane

    @contextlib.contextmanager
    def _flight_lanes(
        self, idx: Index, calls: list[Call], shards: list[int] | None,
        results: list[Any],
    ):
        """The scope ``execute`` and ``execute_batch`` run the BSI lane
        in: the GroupBy lane around it (:meth:`_groupby_lane`), and ONE
        lease over both and over what comes after.  It yields ``late``,
        the list the BSI lane leaves its filtered Sums' launches in,
        enqueued and not awaited (:meth:`_batch_bsi_sums`): they are
        pulled (:meth:`_pull_sums`) once the GroupBy lane's calls in
        flight have ended, so a Sum's program queues behind the first
        levels on the device's one stream and nobody waits for it there.
        A flight without a lane ``GroupBy`` pulls them at the same
        place."""
        late: list = []
        with stacks_mod.reading():
            with self._groupby_lane(idx, calls, shards, results):
                yield late
            self._pull_sums(late, results)

    @contextlib.contextmanager
    def _groupby_lane(
        self, idx: Index, calls: list[Call], shards: list[int] | None,
        results: list[Any],
    ):
        """The GroupBy lane, a scope around the BSI lane: on entry every
        unanswered ``GroupBy`` the batch paths take (:meth:`
        _groupby_batchable`; one whose filter the BSI lane signs stays
        that lane's) has its filter evaluated and its first level's count
        ENQUEUED, call after call and none awaited, so the device counts
        while the BSI lane's host work runs; on exit the calls in flight
        are resumed in turn (pull, prune, enqueue the next level) until
        each has ended.  Same launches as call by call, in another order;
        a flight with one such call runs as it did.  The unfiltered
        two-level call keeps :meth:`_groupby_two_level_batch` and its
        cached cross gram, answered on entry.

        Levels enqueued and not yet pulled hold at most
        ``_GROUPBY_LANE_BUDGET_BYTES`` between them, as the steps reckon
        them: a level that does not fit waits for the oldest pull, and
        one is always admitted.  A later level launches on the stack
        snapshot read on entry, so one lease scope spans entry, the BSI
        lane and exit: another thread's refresh in between copies.

        Steps that return None are answered by the recursive path here,
        once.  Per-item trouble leaves the slot _UNSET for the per-call
        path, which re-raises inside the owning query's demux scope."""
        from pilosa_tpu.exec import astbatch

        taken = [
            i for i, call in enumerate(calls)
            if results[i] is _UNSET and call.name == "GroupBy"
            # a paged call and a call of one level are the per-call path's
            and len(call.children) >= 2 and "previous" not in call.args
            and astbatch.match_bsi(idx, call) is None
        ]
        if not taken:
            yield
            return
        shard_list = self._shards_for(idx, shards)
        stats = self.groupby_lane
        budget = self._GROUPBY_LANE_BUDGET_BYTES
        # per call: (levels, filter row, limit), for the recursive path
        asked: dict[int, tuple] = {}
        # (slot, steps, bytes): asked and held back; enqueued, oldest first
        waiting: collections.deque = collections.deque()
        inflight: collections.deque = collections.deque()
        held = 0

        def answer(i: int, groups) -> None:
            levels, filt_row, limit = asked[i]
            if groups is None:
                groups = self._groupby_recursive(
                    levels, shard_list, filt_row, None, limit
                )
            results[i] = groups[:limit] if limit else groups
            self._count_stat(idx, "GroupBy")

        def resume(i: int, steps, nbytes=None) -> None:
            """Call ``i``'s steps to their next stop: a level enqueued (in
            flight), a level the byte bound holds back (waiting), or
            their end (answered).  ``nbytes``: the level they asked for
            last, now admitted."""
            nonlocal held
            try:
                with tracing.start_span("executor.groupByKLevel").set_tag(
                    "levels", len(asked[i][0])
                ):
                    try:
                        if nbytes is None:
                            nbytes = next(steps)  # pulls the level in flight
                            if waiting or (
                                inflight and held + nbytes > budget
                            ):
                                stats["budget_waits"] += 1
                                waiting.append((i, steps, nbytes))
                                return
                        next(steps)  # enqueues the level
                        inflight.append((i, steps, nbytes))
                        held += nbytes
                    except StopIteration as end:
                        answer(i, end.value)
            except Exception:
                # per-call path re-raises per query
                self._lane_decline("groupby", "error")

        with stacks_mod.reading():
            with tracing.start_span("executor.batchGroupBy") as sp:
                for i in taken:
                    try:
                        levels, filt_row, limit, previous = self._groupby_plan(
                            idx, calls[i], shard_list
                        )
                        if not self._groupby_batchable(levels, previous):
                            continue  # the per-call path's, by design
                        asked[i] = (levels, filt_row, limit)
                        stats["calls"] += 1
                        if len(levels) == 2 and filt_row is None:
                            answer(i, self._groupby_two_level_batch(
                                idx, levels, shard_list
                            ))
                            continue
                    except Exception:
                        self._lane_decline("groupby", "error")
                        continue
                    resume(i, self._groupby_k_level_steps(
                        levels, shard_list, filt_row, deferred=True
                    ))
                sp.set_tag("n", len(asked)).set_tag("levels_max", max(
                    (len(a[0]) for a in asked.values()), default=0
                ))
            yield
            with tracing.start_span("executor.batchGroupBy").set_tag(
                "n", len(inflight) + len(waiting)
            ):
                while inflight or waiting:
                    while waiting and (
                        not inflight or held + waiting[0][2] <= budget
                    ):
                        resume(*waiting.popleft())
                    if not inflight:
                        continue  # the admitted level ended its call
                    i, steps, nbytes = inflight.popleft()
                    stats["pulls"] += 1
                    stats["inflight_sum"] += len(inflight) + 1
                    held -= nbytes
                    resume(i, steps)

    # ------------------------------------------------ batched BSI fast path

    # filter-tensor ceiling for the fused batched Sum ([S, Q, W] uint32
    # per launch; past this the per-query host lane answers instead)
    _BSI_SUM_FILTER_BUDGET_BYTES = 256 << 20

    @staticmethod
    def _bsi_stored_bounds(field: Field, cond: Condition):
        """A condition's bounds in stored space (value - base), encoded
        for the batched kernels (ops/bsi.py condition_bounds)."""
        op = cond.op
        if op == "!=" and cond.value is None:
            return bsi.condition_bounds(op, None)
        if op == "><" or "x" in op:
            lo, hi = cond.int_pair()
            return bsi.condition_bounds(
                op, (lo - field.base, hi - field.base)
            )
        return bsi.condition_bounds(op, int(cond.value) - field.base)

    @staticmethod
    def _sum_valcount(field: Field, tc) -> ValCount:
        total, count = tc
        if count == 0:
            return ValCount()
        return ValCount(value=total + count * field.base, count=count)

    @stacks_mod.reading()
    def _batch_bsi(
        self, idx: Index, calls: list[Call], shards: list[int] | None,
        results: list[Any], late: list,
    ) -> None:
        """Answer every BSI call astbatch signs as batchable with shared
        slice-plane launches: flight-mates group by (field, depth,
        op-class), so Q concurrent range predicates cost ONE
        range_batch/range_count_batch dispatch; range counts
        intersected with set rows group by their filter stacks too, ONE
        launch a group (_batch_bsi_filtered_counts), and so do filtered
        Sums by their filter's shape (_batch_bsi_sums: enqueued here,
        left in ``late`` for :meth:`_flight_lanes` to pull).  Per-item
        trouble leaves the slot _UNSET for the per-call path, which
        re-raises inside the owning query's demux scope — one bad query
        never fails its flight-mates.

        A field engages when >= 2 of its calls batch or its BSI stack is
        already live (the pair-count warm-up economics); a lone cold
        predicate keeps the per-call host latency tier."""
        from pilosa_tpu.exec import astbatch

        by_field: dict[str, list[tuple[int, str, Any, tuple]]] = {}
        fields: dict[str, Field] = {}
        # flight demand per filter (field, view): what builds a cold
        # filter stack, as _batch_general counts it for its leaves
        demand: dict[tuple[str, str], int] = {}
        for i, call in enumerate(calls):
            if results[i] is not _UNSET:
                continue
            m = astbatch.match_bsi(idx, call)
            if m is None:
                continue
            op_class, field, cond, leaves = m
            read = {leaf[:2] for leaf in leaves}
            if op_class == astbatch.BSI_SUM and len(call.children) == 1:
                # a Sum carries its filter's signature where a range
                # class carries its condition (None: answered per call)
                cond = astbatch.match_sum_filter(idx, call.children[0])
                if cond is not None:
                    read = set(cond[1])
            by_field.setdefault(field.name, []).append(
                (i, op_class, cond, leaves)
            )
            fields[field.name] = field
            for pair in read:
                demand[pair] = demand.get(pair, 0) + 1
        if not by_field:
            return

        # the flight's filter stacks by (field, view): _tree_stacks' memo
        memo: dict = {}
        shard_list: list[int] | None = None
        for fname, items in by_field.items():
            field = fields[fname]
            if shard_list is None:
                shard_list = self._shards_for(idx, shards)
            if len(items) < 2 and not self.stacks.bsi_cached(
                field, shard_list
            ):
                self._lane_decline("bsi", "demand")
                continue
            stack = self.stacks.bsi(field, shard_list)
            if stack is None:
                # over budget: per-fragment path answers
                self._lane_decline("bsi", "budget", len(items))
                continue
            bits = stack.bits
            groups: dict[str, list[tuple[int, Any]]] = {}
            # filtered range counts group by their filter stacks too
            filtered: dict[tuple, list[tuple[int, Any, tuple]]] = {}
            for i, op_class, cond, leaves in items:
                if op_class == astbatch.BSI_RANGE_COUNT_FILTERED:
                    pairs = tuple(leaf[:2] for leaf in leaves)
                    filtered.setdefault(pairs, []).append((i, cond, leaves))
                else:
                    groups.setdefault(op_class, []).append((i, cond))
            with tracing.start_span("executor.batchBSI").set_tag(
                "field", fname
            ).set_tag("n", len(items)):
                self._batch_bsi_field(
                    idx, field, stack, bits, groups, shard_list, calls,
                    results, demand, memo, late,
                )
                for pairs, fitems in filtered.items():
                    self._batch_bsi_filtered_counts(
                        idx, field, bits, pairs, fitems, shard_list,
                        demand, results,
                    )

    def _batch_bsi_field(
        self, idx: Index, field: Field, stack, bits, groups, shard_list,
        calls: list[Call], results: list[Any], demand, memo, late: list,
    ) -> None:
        """One field's grouped BSI launches against its live stack."""
        from pilosa_tpu.exec import astbatch
        from pilosa_tpu.ops import kernels

        if kernels.stack_spans_processes(bits):
            # per-shard result words/partials are not host-addressable
            # across processes; the per-call paths keep their own story
            self._lane_decline(
                "bsi", "mesh", sum(len(g) for g in groups.values())
            )
            return
        depth = field.bit_depth
        split: list = []

        def tensors():
            if not split:
                split.append(self._bsi_split(bits))
            return split[0]

        # -- range masks: Range/Row trees and GroupBy filters share ONE
        # [Q, S, W] mask launch
        mask_items = groups.get(astbatch.BSI_RANGE, []) + groups.get(
            astbatch.BSI_GROUPBY, []
        )
        if mask_items:
            try:
                queries = [
                    self._bsi_stored_bounds(field, cond)
                    for _, cond in mask_items
                ]
            except (ValueError, TypeError):
                queries = None
                self._lane_decline("bsi", "error", len(mask_items))
            if queries is not None:
                exists, sign, planes = tensors()
                self.bsi_stack_launches += 1
                with tracing.start_span("executor.bsiRangeBatch").set_tag(
                    "n", len(mask_items)
                ):
                    masks = bsi.range_batch(
                        planes, exists, sign, queries, depth=depth
                    )
                    if getattr(masks, "sharding", None) is not None and len(
                        getattr(masks.sharding, "device_set", ())
                    ) > 1:
                        # one pull for the flight
                        masks = kernels.pull(masks, "bsi_range_batch")
                rows = []
                placed = stacks_mod.positions(shard_list, bits)
                with tracing.start_span("executor.demux").set_tag(
                    "n", len(mask_items)
                ):
                    for qi in range(len(mask_items)):
                        row = Row(n_words=self.holder.n_words)
                        m = masks[qi]
                        for si, s in placed:
                            row.segments[s] = m[si]
                        rows.append(row)
                for (i, _), row in zip(mask_items, rows):
                    if calls[i].name == "GroupBy":
                        try:
                            results[i] = self._execute_groupby(
                                idx, calls[i], shard_list, filt_row=row
                            )
                        except Exception:
                            # per-call path re-raises per query
                            self._lane_decline("bsi", "error")
                    else:
                        results[i] = row

        # -- range counts: agg-cache hits first, the rest share one
        # count launch (no [Q, S, W] materialization)
        count_items = groups.get(astbatch.BSI_RANGE_COUNT, [])
        if count_items:
            pending: list[tuple[int, Any]] = []
            keys: list = []
            for i, cond in count_items:
                keyed = self._range_count_key(idx, calls[i].children[0])
                key = keyed[1] if keyed is not None else None
                cached = (
                    self.stacks.bsi_agg(stack, bits, key)
                    if key is not None else None
                )
                if cached is not None:
                    results[i] = cached
                    self._count_stat(idx)
                else:
                    pending.append((i, cond))
                    keys.append(key)
            if pending:
                try:
                    queries = [
                        self._bsi_stored_bounds(field, cond)
                        for _, cond in pending
                    ]
                except (ValueError, TypeError):
                    queries = None
                    self._lane_decline("bsi", "error", len(pending))
                if queries is not None:
                    exists, sign, planes = tensors()
                    self.bsi_stack_launches += 1
                    with tracing.start_span(
                        "executor.bsiRangeCountBatch"
                    ).set_tag("n", len(pending)):
                        counts = bsi.range_count_batch(
                            planes, exists, sign, queries, depth=depth
                        )
                    with tracing.start_span("executor.demux").set_tag(
                        "n", len(pending)
                    ):
                        for (i, _), key, n in zip(pending, keys, counts):
                            if key is not None:
                                self.stacks.put_bsi_agg(stack, bits, key, n)
                            results[i] = n
                            self._count_stat(idx)

        # -- Sum: unfiltered repeats collapse onto the cached stacked
        # aggregate; filtered Sums share one fused popcount matmul a
        # filter shape when the int32 accumulator and the filter tensor
        # stay in budget
        sum_items = groups.get(astbatch.BSI_SUM, [])
        if sum_items:
            self._batch_bsi_sums(
                idx, field, stack, bits, sum_items, shard_list, calls,
                results, demand, memo, late,
            )

        # -- Min/Max: one cached scalar per (field, kind); grouped here
        # so the flight amortizes the stack build and each item fails
        # alone (cache-served repeats are host dictionary hits)
        for op_class, maximal in (
            (astbatch.BSI_MIN, False), (astbatch.BSI_MAX, True),
        ):
            for i, _ in groups.get(op_class, []):
                try:
                    results[i] = self._execute_min_max(
                        idx, calls[i], shard_list, maximal
                    )
                except Exception:
                    # per-call path re-raises per query
                    self._lane_decline("bsi", "error")

    def _batch_bsi_filtered_counts(
        self, idx: Index, field: Field, bits, pairs, items, shard_list,
        demand, results: list[Any],
    ) -> None:
        """``Count(Intersect(set rows, range predicate))`` items of one
        (int field, filter stacks) group as ONE launch: the filter rows
        are gathered from their field stacks on the device, so only the
        encoded bounds and the row slots leave the host and the BSI
        stack is sliced inside the program.  Stacks sharded over the
        serving mesh run as one SPMD launch, each device on its own
        shards (ops/bsi.py chooses from the operands' layout).  A group
        whose stacks decline leaves its slots _UNSET for the per-call
        path."""
        from pilosa_tpu.ops import kernels

        lane = "bsi_filtered_counts"
        entries = []  # (slot_of, bits) of each filter stack
        for fname, vname in pairs:
            fstack = self._stack_on_demand(
                idx.field(fname), shard_list, vname,
                demand.get((fname, vname), 0),
            )
            if fstack is None:
                # cold and under-demanded, or over budget
                self._lane_decline(lane, "budget", len(items))
                return
            entries.append((fstack.slot_of, fstack.bits))
        layout = kernels.shards_axis_of(bits)
        if kernels.stack_spans_processes(bits) or any(
            kernels.shards_axis_of(e[1]) != layout for e in entries
        ):
            # per-shard partials are not host-addressable across
            # processes, and stacks built under two serving meshes share
            # no program: the per-call path keeps its own story
            self._lane_decline(lane, "mesh", len(items))
            return
        try:
            queries = [
                self._bsi_stored_bounds(field, cond) for _, cond, _ in items
            ]
        except (ValueError, TypeError):
            # the per-call path raises per query
            self._lane_decline(lane, "error", len(items))
            return
        # absent rows -> slot -1 (masked to zero words in the kernel)
        slots = np.array(
            [
                [e[0].get(leaf[2], -1) for e, leaf in zip(entries, leaves)]
                for _, _, leaves in items
            ],
            np.int32,
        )
        self.bsi_stack_launches += 1
        with tracing.start_span(
            "executor.bsiFilteredCountBatch"
        ).set_tag("n", len(items)):
            counts = bsi.range_count_filtered_batch(
                bits, queries, [e[1] for e in entries], slots,
                depth=field.bit_depth,
            )
            with tracing.start_span("executor.demux").set_tag(
                "n", len(items)
            ):
                for (i, _, _), n in zip(items, counts):
                    results[i] = n
                    self._count_stat(idx)

    def _batch_bsi_sums(
        self, idx: Index, field: Field, stack, bits, sum_items, shard_list,
        calls: list[Call], results: list[Any], demand, memo, late: list,
    ) -> None:
        """One int field's ``Sum`` calls.  The unfiltered ones are one
        cached scalar.  A filtered one whose filter astbatch signed
        (``sum_items`` carries the signature) never passes through the
        host: the flight's Sums group by (filter sig, stack pairs), and a
        group is launched in chunks of ``astbatch.SUM_CHUNK`` queries,
        each ONE program over the raw BSI stack and the filter leaves'
        stacks that builds the filter words where they lie.  A lone one
        rides too.  What that path declines (an unsigned tree; a stack
        cold and under-demanded or over budget; stacks under two layouts;
        the int32 gate; the filter tensor's budget) is counted by reason
        and left _UNSET for the per-call path (:meth:`_execute_sum`).
        Nothing is awaited here: every launch goes into ``late``."""
        from pilosa_tpu.exec import astbatch
        from pilosa_tpu.ops import kernels

        depth = field.bit_depth
        unfiltered = [i for i, _ in sum_items if not calls[i].children]
        if unfiltered:
            # every unfiltered Sum in the flight is the SAME scalar:
            # one cached stacked compute answers them all
            try:
                tc = self._bsi_agg_serve(
                    field, (stack, bits, None, shard_list), "sum",
                    lambda p, e, s, fw: bsi.sum_host(
                        p, e, s, fw, depth=depth
                    ),
                )
                for i in unfiltered:
                    results[i] = self._sum_valcount(field, tc)
            except Exception:
                # per-call path re-raises per query
                self._lane_decline("bsi_sums", "error", len(unfiltered))
        # (sig, pairs) -> [(slot, leaves, ranges)]
        groups: dict[tuple, list[tuple]] = {}
        for i, signed in sum_items:
            if not calls[i].children:
                continue
            if signed is None:
                self._sum_decline("shape")
            else:
                sig, pairs, leaves, ranges = signed
                groups.setdefault((sig, pairs), []).append(
                    (i, leaves, ranges)
                )
        # a mesh-sharded stack: each device accumulates, and holds the
        # filter words of, its own shards' share
        layout = kernels.shards_axis_of(bits)
        S_dev = int(bits.shape[0]) // (
            len(bits.sharding.device_set) if layout else 1
        )
        P = astbatch.SUM_CHUNK
        # the int32 accumulator and a chunk's filter words, whatever the tree
        gate = (
            "shape" if not bsi.sum_batch_supported(S_dev, field.n_words)
            else "budget"
            if S_dev * P * field.n_words * 4
            > self._BSI_SUM_FILTER_BUDGET_BYTES
            else None
        )
        for (sig, pairs), items in groups.items():
            st = self._tree_stacks(
                idx, pairs, shard_list, demand, memo, allow_spanning=False
            )
            declined = (
                st if isinstance(st, str)
                # stacks built under two serving meshes share no program
                else "mesh"
                if any(kernels.shards_axis_of(a) != layout for a in st[0])
                else gate
            )
            if declined is not None:
                self._sum_decline(declined, len(items))
                continue
            stacks, slot_maps = st
            ready = []  # (slot, row slots, a bound list a range leaf)
            for i, leaves, ranges in items:
                try:
                    ready.append((
                        i,
                        # absent rows -> slot -1 (zero words in the leaf)
                        [slot_maps[f, vn].get(r, -1) for f, vn, r in leaves],
                        [self._bsi_stored_bounds(f, c) for f, c in ranges],
                    ))
                except (ValueError, TypeError):
                    # the per-call path raises per query
                    self._sum_decline("error")
            depths = [f.bit_depth for f, _ in items[0][2]]
            for at in range(0, len(ready), P):
                chunk = ready[at:at + P]
                slots = np.full((P, len(items[0][1])), -1, np.int32)
                slots[:len(chunk)] = [sl for _, sl, _ in chunk]
                bounds = tuple(
                    bsi.pack_bounds([qb[k] for _, _, qb in chunk], d, P)
                    for k, d in enumerate(depths)
                )
                self.bsi_stack_launches += 1
                self.sum_lane["launches"] += 1
                self.sum_lane["device_filters"] += len(chunk)
                with tracing.start_span("executor.bsiSumBatch").set_tag(
                    "n", len(chunk)
                ):
                    acc = astbatch.run_sum_batch(
                        sig, bits, stacks, slots, bounds, len(chunk)
                    )
                late.append((acc, field, [c[0] for c in chunk]))

    def _sum_decline(self, reason: str, n: int = 1) -> None:
        """``n`` filtered Sums the device-filter path hands to the
        per-call path, which builds their filters on the host."""
        self._lane_decline("bsi_sums", reason, n)
        self.sum_lane["host_filters"] += n

    def _pull_sums(self, late: list, results: list[Any]) -> None:
        """The Sum lane's launches of a flight, pulled, combined and
        demuxed: ``late`` holds (accumulator, summed field, slots) a
        launch.  The device runs its stream in order, so behind the
        GroupBy lane's last pull these are ready.  Booked under
        ``executor.batchBSI`` like the lane that launched them.  Trouble
        with one launch leaves its slots _UNSET for the per-call path."""
        if not late:
            return
        with tracing.start_span("executor.batchBSI").set_tag(
            "n", sum(len(slots) for *_, slots in late)
        ), tracing.start_span("executor.bsiSumPull").set_tag(
            "launches", len(late)
        ):
            for acc, field, slots in late:
                try:
                    pairs = bsi.sum_pairs(
                        acc, depth=field.bit_depth, n=len(slots)
                    )
                except Exception:
                    # per-call path re-raises per query
                    self._lane_decline("bsi_sums", "error", len(slots))
                    continue
                with tracing.start_span("executor.demux").set_tag(
                    "n", len(slots)
                ):
                    for i, tc in zip(slots, pairs):
                        results[i] = self._sum_valcount(field, tc)

    def _bitmap_call(self, idx: Index, call: Call, shards: list[int]) -> Row:
        name = call.name
        if name == planner_mod.SHARED:
            # flight-shared operand (exec/planner.py): the row was
            # materialized once for the whole flight; copy like a cache
            # hit so consumers can attach keys/attrs independently
            return rescache.copy_result(planner_mod.shared_row(call))
        if name in ("Row", "Range"):
            return self._execute_row(idx, call, shards)
        if name == "Difference":
            return self._combine(idx, call, shards, "difference")
        if name == "Intersect":
            return self._combine(idx, call, shards, "intersect")
        if name == "Union":
            return self._combine(idx, call, shards, "union")
        if name == "Xor":
            return self._combine(idx, call, shards, "xor")
        if name == "Not":
            return self._execute_not(idx, call, shards)
        if name == "Shift":
            return self._execute_shift(idx, call, shards)
        raise ExecuteError(f"unknown call: {name}")

    def _combine(self, idx: Index, call: Call, shards: list[int], op: str) -> Row:
        if op == "intersect" and not call.children:
            raise ExecuteError("empty Intersect query is currently not supported")
        if not call.children:
            return Row(n_words=idx.n_words)
        # children evaluate lazily so an Intersect whose running result
        # is provably empty (no populated segments — the planner sorts
        # sparse operands first, exec/planner.py) skips the remaining
        # subtrees entirely
        out = self._bitmap_call(idx, call.children[0], shards)
        for c in call.children[1:]:
            if op == "intersect" and not out.segments:
                break
            out = getattr(out, op)(self._bitmap_call(idx, c, shards))
        return out

    def _execute_not(self, idx: Index, call: Call, shards: list[int]) -> Row:
        """Not() via the _exists field (reference executor.go executeNot)."""
        if not idx.track_existence:
            raise ExecuteError(
                "Not() query requires existence tracking to be enabled"
            )
        if len(call.children) != 1:
            raise ExecuteError("Not() takes one argument")
        ef = idx.existence_field()
        exists = self._field_row(ef, 0, shards)
        child = self._bitmap_call(idx, call.children[0], shards)
        return exists.difference(child)

    def _execute_shift(self, idx: Index, call: Call, shards: list[int]) -> Row:
        if len(call.children) != 1:
            raise ExecuteError("Shift() takes one argument")
        n, ok = call.int_arg("n")
        child = self._bitmap_call(idx, call.children[0], shards)
        # default n=0: unchanged row (reference executor.go:1773)
        return child.shift(n if ok else 0)

    def _field_row(self, field: Field | None, row_id: int, shards: list[int], view: str = VIEW_STANDARD) -> Row:
        """Row segments from the HOST mirrors — the per-call path is the
        latency tier, and the authoritative host copy answers a lone
        read without a device upload or result round trip (the
        throughput tier — batched grams, stacks — lives in
        _batch_pair_counts/_batch_general).  Downstream Row algebra and
        counts dispatch per segment type (exec/result.py)."""
        from pilosa_tpu.ops import kernels

        kernels.record_host_op("field_row")
        out = Row(n_words=self.holder.n_words)
        if field is None:
            return out
        v = field.view(view)
        if v is None:
            return out
        for shard in shards:
            frag = v.fragment(shard)
            if frag is not None:
                out.segments[shard] = frag.row_words_host(row_id)
        return out

    def _execute_row(self, idx: Index, call: Call, shards: list[int]) -> Row:
        """reference executor.go:1444 executeRowShard: plain row, BSI
        condition, or time range."""
        fname = call.field_arg()
        if fname is None:
            raise ExecuteError(f"{call.name}() requires a field argument")
        field = idx.field(fname)
        if field is None:
            raise FieldNotFoundError(f"field not found: {fname}")
        v = call.args.get(fname)
        if isinstance(v, Condition):
            return self._execute_bsi_condition(idx, field, v, shards)
        if "from" in call.args or "to" in call.args:
            return self._execute_time_range(idx, field, call, shards)
        if not isinstance(v, int) or isinstance(v, bool):
            raise ExecuteError(f"{call.name}() row argument must be an integer")
        if field.is_bsi():
            raise ExecuteError(
                f"{call.name}() cannot read a plain row from int field {fname!r}"
            )
        return self._field_row(field, v, shards)

    def _view_cover(self, field: Field, from_arg, to_arg) -> list[str] | None:
        try:
            return timequantum.view_cover(field, from_arg, to_arg, VIEW_STANDARD)
        except ValueError as e:
            raise ExecuteError(str(e))

    def _execute_time_range(self, idx: Index, field: Field, call: Call, shards: list[int]) -> Row:
        """Union of the minimal time-view cover (reference
        executor.go:1515-1531 + time.go viewsByTimeRange)."""
        fname = field.name
        row_id = call.args.get(fname)
        views = self._view_cover(
            field, call.args.get("from"), call.args.get("to")
        )
        out = Row(n_words=idx.n_words)
        if views is None:
            return out
        for vname in views:
            out = out.union(self._field_row(field, row_id, shards, view=vname))
        return out

    def _execute_bsi_condition(self, idx: Index, field: Field, cond: Condition, shards: list[int]) -> Row:
        """BSI range predicate -> bit-plane kernels (reference
        executor.go:1536-1566 executeBSIGroupRangeShard +
        fragment.go:1271-1534)."""
        if not field.is_bsi():
            raise ExecuteError(
                f"range condition on non-int field {field.name!r}"
            )
        # ONE warm-up decision per condition (a != evaluates two
        # kernels; they must not double-count demand)
        ready = self._bsi_single_ready(field, shards)
        op = cond.op
        if op == "!=" and cond.value is None:
            # f != null -> not-null (reference frag.notNull)
            return self._bsi_rows(
                field, shards, lambda pl, ex, sg: ex, ready=ready
            )
        if op == "==" and cond.value is None:
            raise ExecuteError("Range(): <field> == null is not supported")
        depth = field.bit_depth
        base = field.base

        if op in ("<", "<=", ">", ">="):
            bound = int(cond.value) - base
            fn = bsi.range_lt if op in ("<", "<=") else bsi.range_gt
            allow_eq = op in ("<=", ">=")
            return self._bsi_rows(
                field,
                shards,
                lambda pl, ex, sg: fn(
                    pl, ex, sg, value=bound, depth=depth, allow_eq=allow_eq
                ),
                ready=ready,
            )
        if op in ("==", "!="):
            stored = int(cond.value) - base
            eq = self._bsi_rows(
                field,
                shards,
                lambda pl, ex, sg: bsi.range_eq(
                    pl, ex, sg, value_abs=abs(stored), negative=stored < 0, depth=depth
                ),
                ready=ready,
            )
            if op == "==":
                return eq
            notnull = self._bsi_rows(
                field, shards, lambda pl, ex, sg: ex, ready=ready
            )
            return notnull.difference(eq)
        if op == "><":
            lo, hi = cond.int_pair()
            return self._bsi_rows(
                field,
                shards,
                lambda pl, ex, sg: bsi.range_between(
                    pl, ex, sg, lo=lo - base, hi=hi - base, depth=depth
                ),
                ready=ready,
            )
        if op in ("<x<", "<=x<", "<x<=", "<=x<="):
            lo, hi = cond.int_pair()
            lo_op, hi_op = op.split("x")
            lo_incl = lo if lo_op == "<=" else lo + 1
            hi_incl = hi if hi_op == "<=" else hi - 1
            return self._bsi_rows(
                field,
                shards,
                lambda pl, ex, sg: bsi.range_between(
                    pl, ex, sg, lo=lo_incl - base, hi=hi_incl - base, depth=depth
                ),
                ready=ready,
            )
        raise ExecuteError(f"unsupported condition op: {op}")

    @staticmethod
    def _bsi_split(bits):
        """(exists, sign, planes) slices of a raw BSI stack.  Each slice
        is a device dispatch, so callers split only when they actually
        compute — a cache-served aggregate never pays it."""
        with tracing.start_span("executor.bsiSplit"):
            return bits[:, 0], bits[:, 1], bits[:, 2:]

    @staticmethod
    def _host_cpu_device():
        """The in-process CPU device for latency-tier kernel runs (the
        CPU backend coexists with the accelerator backend), or None."""
        try:
            return jax.local_devices(backend="cpu")[0]
        except Exception:
            return None

    # lone BSI predicates seen before the stack investment is judged
    # worthwhile (the BSI twin of _PAIR_SINGLE_WARM; 0 = invest on the
    # first query, i.e. the pre-round-4 behavior)
    _BSI_SINGLE_WARM = 4

    def _bsi_single_ready(self, field: Field, shards: list[int]) -> bool:
        """Whether a LONE BSI predicate should take the device stack
        path — mirror of _pair_single_ready's warm-up economics: a live
        stack serves immediately; otherwise repeat demand must justify
        the full-field device upload before a lone query pays it."""
        if self._BSI_SINGLE_WARM <= 0:
            return True
        if self.stacks.bsi_cached(field, shards):
            return True
        return stacks_mod.note_single(field, "bsi") >= self._BSI_SINGLE_WARM

    def _bsi_rows(
        self, field: Field, shards: list[int], kernel,
        ready: bool | None = None,
    ) -> Row:
        """Evaluate a BSI predicate kernel over every shard.  The kernels
        are shape-polymorphic (ops/bsi.py), so the stacked path runs the
        SAME compiled scan over [S, depth, W] in one launch; without a
        stack (over budget) each fragment launches separately.

        Latency tier: a LONE COLD predicate (no live stack, warm-up not
        reached) runs the SAME kernel on the in-process CPU backend over
        the fragment host mirrors — one compile per shape, then pure
        host execution, no device upload (the BSI twin of the host
        pair-count tier)."""
        out = Row(n_words=self.holder.n_words)
        if ready is None:
            ready = self._bsi_single_ready(field, shards)
        cpu = self._host_cpu_device()
        if cpu is not None and not ready:
            view = field.view(field.bsi_view_name())
            if view is None:
                return out
            frags = [
                (s, view.fragment(s))
                for s in shards
                if view.fragment(s) is not None
            ]
            if not frags:
                return out
            depth = field.bit_depth
            # ONE preallocated stacked buffer filled in place: the cold
            # query costs exactly one field-sized host copy, not three
            W = field.n_words
            planes = np.zeros((len(frags), depth, W), dtype=np.uint32)
            exists = np.zeros((len(frags), W), dtype=np.uint32)
            sign = np.zeros((len(frags), W), dtype=np.uint32)
            for si, (_, f) in enumerate(frags):
                f.fill_bsi_tensors_host(
                    depth, planes[si], exists[si], sign[si]
                )
            with _DL_STACK.launch(
                sig=f"bsi_rows/host d{depth}"
            ), jax.default_device(cpu):
                mask = np.asarray(
                    kernel(
                        jnp.asarray(planes), jnp.asarray(exists),
                        jnp.asarray(sign),
                    )
                )
            for si, (s, _) in enumerate(frags):
                out.segments[s] = mask[si]
            return out
        stack = self.stacks.bsi(field, shards)
        if stack is not None:
            bits = stack.bits
            exists, sign, planes = self._bsi_split(bits)
            self.bsi_stack_launches += 1
            from pilosa_tpu.ops import kernels

            with _DL_STACK.launch(
                sig=f"bsi_rows/stack d{field.bit_depth}"
            ), kernels.enqueue("bsi_rows"):
                mask = kernel(planes, exists, sign)  # [S, W], one launch
            if getattr(mask, "sharding", None) is not None and len(
                getattr(mask.sharding, "device_set", ())
            ) > 1:
                # one pull; avoid mixed placements
                mask = kernels.pull(mask, "bsi_rows")
            for si, s in stacks_mod.positions(shards, bits):
                out.segments[s] = mask[si]
            return out
        view = field.view(field.bsi_view_name())
        if view is None:
            return out
        for shard in shards:
            frag = view.fragment(shard)
            if frag is None:
                continue
            planes, exists, sign = frag.bsi_tensors(field.bit_depth)
            with _DL_STACK.launch(sig=f"bsi_rows/frag d{field.bit_depth}"):
                out.segments[shard] = kernel(planes, exists, sign)
        return out

    # ------------------------------------------------------------ aggregates

    def _range_count_key(self, idx: Index, child: Call):
        """(field, cache key) when ``child`` is a pure BSI range
        predicate — the repeat-dashboard shape ``Count(Range(v < N))``
        whose answer is a per-snapshot scalar; None otherwise."""
        if child.name not in ("Row", "Range") or child.children:
            return None
        fname = child.field_arg()
        if fname is None or set(child.args) != {fname}:
            return None
        field = idx.field(fname)
        if field is None or not field.is_bsi():
            return None
        cond = child.args.get(fname)
        if not isinstance(cond, Condition):
            return None
        v = cond.value
        if isinstance(v, list):
            v = tuple(v)
        return field, f"rangecount:{cond.op}:{v!r}"

    def _execute_count(self, idx: Index, call: Call, shards: list[int] | None) -> int:
        if len(call.children) != 1:
            raise ExecuteError("Count() takes one argument")
        child = call.children[0]
        shard_list = self._shards_for(idx, shards)
        keyed = self._range_count_key(idx, child)
        if keyed is not None:
            field, key = keyed
            # peek, never build: a lone cold range count must not pay a
            # full-field device upload for the agg cache (the host BSI
            # tier below answers it; repeat demand builds the stack)
            ready = self._BSI_SINGLE_WARM <= 0 or self.stacks.bsi_cached(
                field, shard_list
            )
            stack = self.stacks.bsi(field, shard_list) if ready else None
            if stack is not None:
                bits = stack.bits
                cached = self.stacks.bsi_agg(stack, bits, key)
                if cached is not None:
                    return cached
                n = self._bitmap_call(idx, child, shard_list).count()
                self.stacks.put_bsi_agg(stack, bits, key, n)
                return n
        # Latency tier: a lone Count over a pair or single row — the
        # gram fast path has already declined (cold field / single
        # query), so answer from the host mirrors with the fused native
        # kernel, zero copies (reference executor.go:1792 Count through
        # roaring.go:568's word loop).
        m = self._match_pair_count(idx, call)
        if m is not None:
            fname, op, ra, rb = m
            view = idx.field(fname).view(VIEW_STANDARD)
            t0 = time.perf_counter()
            total = self._host_pair_count(view, ra, rb, op, shard_list)
            # host-lane price note: what the lane chooser weighs against
            # the ledger's measured gram cost (exec/planner.py)
            self.planner.note_host_lane(
                "pair_count", (time.perf_counter() - t0) * 1e3
            )
            return total
        n = self._match_single_row_count(idx, child)
        if n is not None:
            field, row_id = n
            view = field.view(VIEW_STANDARD)
            if view is not None:
                # popcount(a) == popcount(a & a): ride the same fused
                # batched path as pair counts
                return self._host_pair_count(
                    view, row_id, row_id, "intersect", shard_list
                )
            return 0
        if child.name in (
            "Intersect", "Union", "Difference", "Xor", "Not"
        ) and not planner_mod.contains_shared(child):
            # solo host evaluation of a full tree: the batch-vs-solo
            # host-lane price (post-CSE combines are excluded — a
            # grafted tree is not a solo-evaluation sample)
            t0 = time.perf_counter()
            total = self._bitmap_call(idx, child, shard_list).count()
            self.planner.note_host_lane(
                "tree_count", (time.perf_counter() - t0) * 1e3
            )
            return total
        return self._bitmap_call(idx, child, shard_list).count()

    @staticmethod
    def _match_single_row_count(idx: Index, child: Call):
        """(field, row_id) when ``child`` is a plain ``Row(f=<id>)`` over
        a set-like field's standard view; None otherwise."""
        if child.name != "Row" or child.children:
            return None
        fname = child.field_arg()
        if fname is None or set(child.args) != {fname}:
            return None
        v = child.args.get(fname)
        if not isinstance(v, int) or isinstance(v, bool):
            return None
        field = idx.field(fname)
        if field is None or field.field_type == FIELD_TYPE_INT:
            return None
        return field, v

    # shards per latency-tier fan-out chunk; also the engage threshold —
    # below it the per-thread handoff costs more than it saves
    _HOST_FANOUT_CHUNK = 24

    def _host_pair_count(self, view, ra: int, rb: int, op: str, shard_list: list[int]) -> int:
        """Sum of fused host pair counts across shards, batched into ONE
        native call per chunk (per-shard ctypes crossings would cost
        more than the count itself at 100+ shards) and fanned across a
        small thread pool when the host has cores to use (the native
        kernel releases the GIL, so shard chunks count in parallel —
        the worker-pool role of reference executor.go:2557-2611)."""
        if view is None:
            return 0
        from pilosa_tpu.ops import kernels

        kernels.record_host_op("host_pair_count")
        frags = [
            f for f in (view.fragment(s) for s in shard_list) if f is not None
        ]
        if not frags:
            return 0
        cores = os.cpu_count() or 1
        if cores > 1 and len(frags) >= 2 * self._HOST_FANOUT_CHUNK:
            chunks = [
                frags[i : i + self._HOST_FANOUT_CHUNK]
                for i in range(0, len(frags), self._HOST_FANOUT_CHUNK)
            ]
            pool = self._host_tier_pool()
            return sum(
                pool.map(
                    lambda ch: self._host_pair_count_chunk(ch, ra, rb, op),
                    chunks,
                )
            )
        return self._host_pair_count_chunk(frags, ra, rb, op)

    @staticmethod
    def _host_pair_count_chunk(frags, ra: int, rb: int, op: str) -> int:
        """One fused native crossing for a chunk of fragments, with every
        fragment's lock held through the call so counts read a
        consistent snapshot (absent rows ride a shared zeros row, which
        yields the zero-row semantics of every op).  Row addresses are
        computed vectorized (base + slot*stride) so the whole fan costs
        one ctypes call and zero per-row marshalling.  Falls back to the
        per-fragment path when the native library is absent."""
        import contextlib

        from pilosa_tpu.ops import _hostops

        if _hostops.load() is None:
            return sum(f.row_pair_count(ra, rb, op) for f in frags)
        n = len(frags)
        n_words = frags[0].n_words
        zeros = np.zeros(n_words, dtype=np.uint32)
        zaddr = zeros.__array_interface__["data"][0]
        bases = np.empty(n, dtype=np.uint64)
        slots_a = np.empty(n, dtype=np.int64)
        slots_b = np.empty(n, dtype=np.int64)
        hosts = []  # keep every backing array alive through the call
        with contextlib.ExitStack() as st:
            for i, f in enumerate(frags):
                st.enter_context(f._lock)
                hosts.append(f._host)  # keep alive through the call
                bases[i] = f._host_addr  # maintained at _host reassignment
                sa = f._slot_of.get(ra)
                sb = f._slot_of.get(rb)
                slots_a[i] = -1 if sa is None else sa
                slots_b[i] = -1 if sb is None else sb
            stride = np.uint64(n_words * 4)
            addr_a = np.where(
                slots_a < 0, np.uint64(zaddr),
                bases + slots_a.astype(np.uint64) * stride,
            )
            addr_b = np.where(
                slots_b < 0, np.uint64(zaddr),
                bases + slots_b.astype(np.uint64) * stride,
            )
            total = _hostops.pair_count_addrs(addr_a, addr_b, n_words, op)
        if total is None:  # race: library vanished; serial fallback
            return sum(f.row_pair_count(ra, rb, op) for f in frags)
        return total

    # guards _host_pool creation: concurrent request threads must not
    # each build (and leak) a pool — same discipline as
    # DistributedExecutor._fanout_pool
    _host_pool_lock = threading.Lock()

    def _host_tier_pool(self):
        """Lazily built, executor-lifetime thread pool for latency-tier
        shard fan-out (never built on single-core hosts)."""
        pool = getattr(self, "_host_pool", None)
        if pool is None:
            import concurrent.futures

            with self._host_pool_lock:
                pool = getattr(self, "_host_pool", None)
                if pool is None:
                    pool = concurrent.futures.ThreadPoolExecutor(
                        max_workers=min(8, os.cpu_count() or 1),
                        thread_name_prefix="pilosa-hosttier",
                    )
                    self._host_pool = pool
        return pool

    def _sum_filter(self, idx: Index, call: Call, shards: list[int]):
        if len(call.children) > 1:
            raise ExecuteError(f"{call.name}() only accepts a single bitmap input")
        if call.children:
            return self._bitmap_call(idx, call.children[0], shards)
        return None

    def _bsi_field(self, idx: Index, call: Call) -> Field:
        fname, ok = call.string_arg("field")
        if not ok:
            fname = call.args.get("_field")
        if not fname:
            raise ExecuteError(f"{call.name}(): field required")
        field = idx.field(fname)
        if field is None:
            raise FieldNotFoundError(f"field not found: {fname}")
        return field

    def _bsi_agg_shards(self, idx: Index, call: Call, shards: list[int] | None):
        """Shared scaffold for Sum/Min/Max: resolve the BSI field and the
        optional filter child; returns (field, stacked_or_None,
        per_shard_generator).  The stacked form is a DEFERRED
        (stack, raw_bits, filter_row, shards) tuple — ``_bsi_tensors``
        materializes the (planes, exists, sign, filter-words) views on a
        cache miss, answering the aggregate in one launch; the generator
        is the per-fragment fallback when the stack declines (over
        budget)."""
        shards = self._shards_for(idx, shards)
        field = self._bsi_field(idx, call)
        filt = self._sum_filter(idx, call, shards)
        view = field.view(field.bsi_view_name())

        stacked = None
        stack = self.stacks.bsi(field, shards)
        if stack is not None:
            # split + filter materialization deferred to _bsi_tensors:
            # a cache-served aggregate pays zero device dispatches
            stacked = (stack, stack.bits, filt, shards)

        def per_shard():
            if view is None:
                return
            ones = np.full(field.n_words, 0xFFFFFFFF, dtype=np.uint32)
            for shard in shards:
                frag = view.fragment(shard)
                if frag is None:
                    continue
                fw = ones
                if filt is not None:
                    fw = filt.segments.get(shard)
                    if fw is None:
                        continue
                planes, exists, sign = frag.bsi_tensors(field.bit_depth)
                yield planes, exists, sign, fw

        return field, stacked, per_shard()

    def _bsi_tensors(self, field: Field, stacked):
        """Materialize a deferred stacked tuple: split the raw stack and
        build the filter words (device dispatches — run only on a cache
        miss)."""
        _, bits, filt, shards = stacked
        exists, sign, planes = self._bsi_split(bits)
        if filt is None:
            # the kernels compute f = exists & filter, so exists
            # itself is the identity filter — no index-width upload
            fw = exists
        else:
            # the stack's shard axis is padded to the mesh size;
            # padded slices have exists == 0, so any filter value
            # there is inert
            from pilosa_tpu.ops import kernels

            fw_np = self._row_to_shard_matrix(filt, shards, bits)
            sh = getattr(exists, "sharding", None)
            multi = sh is not None and len(getattr(sh, "device_set", ())) > 1
            # co-locate with a sharded stack
            fw = kernels.h2d(fw_np, sh if multi else None)
            _DL_STACK.record_transfer(fw_np.nbytes, "h2d")
        return planes, exists, sign, fw

    def _bsi_agg_serve(self, field: Field, stacked, key: str, compute):
        """Serve one stacked aggregate: per-snapshot cache hit for
        unfiltered queries, else materialize the tensors, run
        ``compute(planes, exists, sign, fw)``, and install (filtered
        queries always compute — their result depends on the filter)."""
        from pilosa_tpu.ops import kernels

        stack, bits, filt, _ = stacked
        cached = (
            self.stacks.bsi_agg(stack, bits, key) if filt is None else None
        )
        if cached is None:
            planes, exists, sign, fw = self._bsi_tensors(field, stacked)
            self.bsi_stack_launches += 1
            with _DL_STACK.launch(sig=f"bsi_agg/{key.split(':', 1)[0]}") as w:
                w.mesh = kernels._multi_device(planes)
                cached = compute(planes, exists, sign, fw)
            if filt is None:
                self.stacks.put_bsi_agg(stack, bits, key, cached)
        return cached

    def _execute_sum(self, idx: Index, call: Call, shards: list[int] | None) -> ValCount:
        """reference executor.go:409-442 + executeSumCountShard."""
        field, stacked, tensors = self._bsi_agg_shards(idx, call, shards)
        if stacked is not None:
            total, count = self._bsi_agg_serve(
                field,
                stacked,
                "sum",
                lambda p, e, s, fw: bsi.sum_host(
                    p, e, s, fw, depth=field.bit_depth
                ),
            )
            if count == 0:
                return ValCount()
            return ValCount(value=total + count * field.base, count=count)
        total, count = 0, 0
        for planes, exists, sign, fw in tensors:
            s, c = bsi.sum_host(planes, exists, sign, fw, depth=field.bit_depth)
            total += s
            count += c
        if count == 0:
            return ValCount()
        return ValCount(value=total + count * field.base, count=count)

    def _execute_min_max(self, idx: Index, call: Call, shards: list[int] | None, maximal: bool) -> ValCount:
        field, stacked, tensors = self._bsi_agg_shards(idx, call, shards)
        if stacked is not None:
            # the stacked kernels reduce candidates globally across the
            # shard axis, which IS the per-shard merge (equal extremes
            # accumulate their counts)
            value, count = self._bsi_agg_serve(
                field,
                stacked,
                f"minmax:{maximal}",
                lambda p, e, s, fw: bsi.min_max_host(
                    p, e, s, fw, depth=field.bit_depth, maximal=maximal
                ),
            )
            if count == 0:
                return ValCount()
            return ValCount(value=value + field.base, count=count)
        best: ValCount | None = None
        for planes, exists, sign, fw in tensors:
            value, count = bsi.min_max_host(
                planes, exists, sign, fw, depth=field.bit_depth, maximal=maximal
            )
            if count == 0:
                continue
            value += field.base
            if best is None or (value > best.value if maximal else value < best.value):
                best = ValCount(value=value, count=count)
            elif value == best.value:
                best.count += count
        return best or ValCount()

    def _execute_min_max_row(self, idx: Index, call: Call, shards: list[int] | None, maximal: bool) -> Pair:
        """MinRow/MaxRow: extreme existing row id (reference
        executor.go:560-651)."""
        shards = self._shards_for(idx, shards)
        fname, ok = call.string_arg("field")
        if not ok:
            raise ExecuteError(f"{call.name}(): field required")
        field = idx.field(fname)
        if field is None:
            raise FieldNotFoundError(f"field not found: {fname}")
        view = field.view(VIEW_STANDARD)
        best: Pair | None = None
        if view is not None:
            for shard in shards:
                frag = view.fragment(shard)
                if frag is None:
                    continue
                ids, counts = frag.row_counts()
                # uint64: row ids span the full 64-bit space
                ids = np.asarray(ids, np.uint64)
                counts = np.asarray(counts, np.int64)
                nz = counts > 0  # vectorized extreme instead of a
                if not nz.any():  # per-row Python scan
                    continue
                rid = int(ids[nz].max() if maximal else ids[nz].min())
                cnt = int(counts[ids == rid][0])
                if best is None or (
                    rid > best.id if maximal else rid < best.id
                ):
                    best = Pair(id=rid, count=cnt)
                elif rid == best.id:
                    best.count += cnt
        return best or Pair()

    # ------------------------------------------------------------- mutations

    def _execute_set(self, idx: Index, call: Call) -> bool:
        """reference executor.go:2069 executeSet."""
        col, ok = call.uint_arg("_col")
        if not ok:
            raise ExecuteError("Set() column argument 'col' required")
        fname = call.field_arg()
        if fname is None:
            raise ExecuteError("Set() argument required: field")
        field = idx.field(fname)
        if field is None:
            raise FieldNotFoundError(f"field not found: {fname}")
        idx.add_column_existence(col)
        if field.is_bsi():
            value, ok = call.int_arg(fname)
            if not ok:
                raise ExecuteError("Set() row argument 'row' required")
            return field.set_value(col, value)
        row, ok = call.uint_arg(fname)
        if not ok:
            raise ExecuteError("Set() row argument 'row' required")
        ts = call.args.get("_timestamp")
        timestamp = timequantum.parse_time(ts) if ts is not None else None
        return field.set_bit(row, col, timestamp)

    def _execute_clear(self, idx: Index, call: Call) -> bool:
        col, ok = call.uint_arg("_col")
        if not ok:
            raise ExecuteError("Clear() column argument required")
        fname = call.field_arg()
        if fname is None:
            raise ExecuteError("Clear() argument required: field")
        field = idx.field(fname)
        if field is None:
            raise FieldNotFoundError(f"field not found: {fname}")
        if field.is_bsi():
            # reference semantics: Clear on an int field clears nothing via
            # the standard view; we clear the stored value when the arg
            # matches the column's current value is NOT checked (v1.3
            # behavior: ClearBit on bsi fields is a no-op through views).
            return field.clear_value(col)
        row, ok = call.uint_arg(fname)
        if not ok:
            raise ExecuteError("row=<row> argument required to Clear() call")
        return field.clear_bit(row, col)

    def _execute_clear_row(self, idx: Index, call: Call, shards: list[int] | None) -> bool:
        """reference executor.go:1899-1997."""
        fname = call.field_arg()
        if fname is None:
            raise ExecuteError("ClearRow() argument required: field")
        field = idx.field(fname)
        if field is None:
            raise FieldNotFoundError(f"field not found: {fname}")
        if field.field_type not in ("set", "time", "mutex", "bool"):
            raise ExecuteError(
                f"ClearRow() is not supported on {field.field_type} fields"
            )
        row = call.args.get(fname)
        if not isinstance(row, int) or isinstance(row, bool):
            raise ExecuteError("ClearRow() requires a row argument")
        changed = False
        v = field.view(VIEW_STANDARD)
        if v is not None:
            for shard in self._shards_for(idx, shards):
                frag = v.fragment(shard)
                if frag is not None:
                    changed |= frag.clear_row(row)
        return changed

    def _execute_store(self, idx: Index, call: Call, shards: list[int] | None) -> bool:
        """Store(child, f=row): write child bitmap as a row (reference
        executor.go:1999-2067 executeSetRow)."""
        if len(call.children) != 1:
            raise ExecuteError("Store() requires a source query")
        fname = call.field_arg()
        if fname is None:
            raise ExecuteError("Store() argument required: field")
        field = idx.field(fname)
        if field is None:
            # reference creates a set field on demand for Store
            # (executor.go:2016-2023).
            field = idx.create_field(fname)
        row = call.args.get(fname)
        if not isinstance(row, int) or isinstance(row, bool):
            raise ExecuteError("Store() requires a row argument")
        shards = self._shards_for(idx, shards)
        child = self._bitmap_call(idx, call.children[0], shards)
        view = field.create_view_if_not_exists(VIEW_STANDARD)
        changed = False
        for shard in shards:
            seg = child.segments.get(shard)
            words = (
                np.zeros(field.n_words, dtype=np.uint32)
                if seg is None
                else np.asarray(seg)
            )
            frag = view.create_fragment_if_not_exists(shard)
            changed |= frag.set_row_words(row, words)
        return changed

    def _execute_set_row_attrs(self, idx: Index, call: Call) -> None:
        fname, ok = call.string_arg("_field")
        field = idx.field(fname) if ok else None
        if field is None:
            raise FieldNotFoundError("SetRowAttrs() field not found")
        row, ok = call.uint_arg("_row")
        if not ok:
            raise ExecuteError("SetRowAttrs() row required")
        attrs = {
            k: v for k, v in call.args.items() if k not in ("_field", "_row")
        }
        field.row_attrs.set_attrs(row, attrs)
        return None

    def _execute_set_column_attrs(self, idx: Index, call: Call) -> None:
        col, ok = call.uint_arg("_col")
        if not ok:
            raise ExecuteError("SetColumnAttrs() column required")
        attrs = {k: v for k, v in call.args.items() if k != "_col"}
        idx.column_attrs.set_attrs(col, attrs)
        return None

    # ------------------------------------------------------------------ TopN

    def _execute_topn(self, idx: Index, call: Call, shards: list[int] | None) -> list[Pair]:
        """Exact TopN (reference executor.go:860-999 is two-phase because
        per-shard caches are approximate; device row counts are exact, so a
        single pass suffices and strictly dominates the reference's
        accuracy)."""
        shards = self._shards_for(idx, shards)
        fname, ok = call.string_arg("_field")
        if not ok:
            raise ExecuteError("TopN() field required")
        field = idx.field(fname)
        if field is None:
            raise FieldNotFoundError(f"field not found: {fname}")
        if field.is_bsi():
            raise ExecuteError(f"cannot compute TopN() on integer field: {fname!r}")
        if field.options.cache_type == "none":
            raise ExecuteError(f"cannot compute TopN(), field has no cache: {fname!r}")
        n, _ = call.uint_arg("n")
        ids_arg, has_ids = call.uint_slice_arg("ids")
        threshold, has_threshold = call.uint_arg("threshold")
        if not has_threshold or threshold == 0:
            threshold = DEFAULT_MIN_THRESHOLD
        tanimoto, has_tanimoto = call.uint_arg("tanimotoThreshold")
        if has_tanimoto and tanimoto > 100:
            raise ExecuteError("Tanimoto Threshold is from 1 to 100 only")
        attr_name, _ = call.string_arg("attrName")
        attr_values = call.args.get("attrValues")

        src: Row | None = None
        if len(call.children) == 1:
            src = self._bitmap_call(idx, call.children[0], shards)
        elif len(call.children) > 1:
            raise ExecuteError("TopN() can only have one input bitmap")

        view = field.view(VIEW_STANDARD)
        counts: dict[int, int] = {}
        src_count = src.count() if src is not None else 0
        row_totals: dict[int, int] = {}
        # Two-tier dispatch: UNFILTERED TopN is served from the
        # MAINTAINED per-fragment counts (host, no device work, stays
        # correct across writes via the import/point-write delta
        # carrying — the reference's ranked cache, cache.go:158); the
        # stack path is the throughput tier for FILTERED TopN where a
        # masked-count kernel earns its launch.
        if view is not None and src is not None:
            # One launch over the cached field stack answers every
            # (shard, row) at once via the masked-count kernel (replacing
            # the reference's per-fragment cache merge and the per-shard
            # filter loop, fragment.go:1586-1655).
            from pilosa_tpu.ops import kernels

            stack = self.stacks.get(field, shards)
            bits = stack.bits if stack is not None else None
            # masked counts run in-program (psum) on process-spanning
            # stacks too; the only decline left is totals past even a
            # single-shard psum slice's int32 bound — the per-fragment
            # loop below answers then
            if stack is not None and kernels.row_counts_supported(bits):
                slot_of = stack.slot_of
                filt = self._row_to_shard_matrix(src, shards, bits)
                mc = kernels.masked_row_counts(bits, filt)
                rc = (
                    self.stacks.row_counts(stack, bits)
                    if has_tanimoto else None
                )
                with tracing.start_span("executor.demux").set_tag(
                    "n", len(slot_of)
                ):
                    for rid, slot in slot_of.items():
                        if mc[slot]:
                            counts[rid] = int(mc[slot])
                        if rc is not None and rc[slot]:
                            row_totals[rid] = int(rc[slot])
                view = None  # stack covered every shard; skip the loop
        if view is not None and src is None:
            # vectorized merge of the maintained per-fragment counts:
            # concatenate (ids, counts) across shards and reduce by row
            # id — no per-(shard, row) Python work
            id_parts: list[np.ndarray] = []
            count_parts: list[np.ndarray] = []
            for shard in shards:
                frag = view.fragment(shard)
                if frag is None:
                    continue
                ids, row_counts = frag.row_counts()
                if ids:
                    id_parts.append(np.asarray(ids, dtype=np.int64))
                    count_parts.append(row_counts)
            if id_parts:
                cat_ids = np.concatenate(id_parts)
                cat_counts = np.concatenate(count_parts)
                uids, inv = np.unique(cat_ids, return_inverse=True)
                sums = np.bincount(
                    inv, weights=cat_counts, minlength=len(uids)
                ).astype(np.int64)
                nz = sums > 0
                counts = {
                    int(r): int(c)
                    for r, c in zip(uids[nz], sums[nz])
                }
            view = None  # merged every shard; skip the loop below
        if view is not None:
            for shard in shards:
                frag = view.fragment(shard)
                if frag is None:
                    continue
                # this loop only runs FILTERED (src set): the unfiltered
                # case merged maintained counts above
                ids, row_counts = frag.row_counts()
                if has_tanimoto:
                    # Row totals accumulate over every shard the row
                    # exists in, even where the src bitmap is empty —
                    # the tanimoto denominator needs the full row
                    # cardinality.
                    for rid, t in zip(ids, row_counts.tolist()):
                        row_totals[rid] = row_totals.get(rid, 0) + t
                seg = src.segments.get(shard)
                if seg is None:
                    continue
                if isinstance(seg, np.ndarray):
                    # host-tier filter: fused count against the host
                    # mirror, no device round trip
                    mids, matrix = frag.rows_matrix_host()
                    inter = np.bitwise_count(
                        matrix & seg[None, :]
                    ).sum(axis=1, dtype=np.int64)
                    ids = mids
                else:
                    inter = kernels.pull(
                        bitops.count_rows(
                            frag.rows_device(ids) & seg[None, :]
                        ),
                        "count_rows",
                    )
                for rid, c in zip(ids, inter.tolist()):
                    if c:
                        counts[rid] = counts.get(rid, 0) + c

        if has_ids and ids_arg is not None:
            counts = {r: counts.get(r, 0) for r in ids_arg}
        if attr_name:
            wanted = set()
            if isinstance(attr_values, list):
                wanted = {v for v in attr_values}
            keep = {}
            for rid, c in counts.items():
                av = field.row_attrs.attrs(rid).get(attr_name)
                if av is not None and (not wanted or av in wanted):
                    keep[rid] = c
            counts = keep
        if has_tanimoto and src is not None:
            keep = {}
            for rid, c in counts.items():
                denom = row_totals.get(rid, 0) + src_count - c
                if denom > 0 and c * 100 >= tanimoto * denom:
                    keep[rid] = c
            counts = keep
        pairs = [
            Pair(id=rid, count=c)
            for rid, c in counts.items()
            if c >= threshold or has_ids
        ]
        pairs.sort(key=lambda p: (-p.count, p.id))
        if n and not has_ids:
            pairs = pairs[:n]
        return pairs

    # ------------------------------------------------------------------ Rows

    def _rows_of_field(
        self,
        field: Field,
        shards: list[int],
        views: list[str] | None = None,
    ) -> list[int]:
        """Sorted distinct row ids with at least one bit (reference
        fragment.go:2601-2712 rows())."""
        ids: set[int] = set()
        for vname in [VIEW_STANDARD] if views is None else views:
            v = field.view(vname)
            if v is None:
                continue
            for shard in shards:
                frag = v.fragment(shard)
                if frag is None:
                    continue
                rids, counts = frag.row_counts()
                ids.update(r for r, c in zip(rids, counts.tolist()) if c > 0)
        return sorted(ids)

    def _execute_rows(self, idx: Index, call: Call, shards: list[int] | None) -> RowIdentifiers:
        """reference executor.go:1277-1442 executeRows."""
        shards = self._shards_for(idx, shards)
        fname, ok = call.string_arg("_field")
        if not ok:
            raise ExecuteError("Rows() field required")
        field = idx.field(fname)
        if field is None:
            raise FieldNotFoundError(f"field not found: {fname}")
        views = self._rows_views(field, call)
        ids = self._rows_of_field(field, shards, views)

        col = call.args.get("column")
        if col is not None:
            col = self._maybe_translate_col(idx, col)
            shard = col // (field.n_words * 32)
            off = col % (field.n_words * 32)
            present: set[int] = set()
            for vname in [VIEW_STANDARD] if views is None else views:
                v = field.view(vname)
                if v is None:
                    continue
                frag = v.fragment(shard)
                if frag is None:
                    continue
                # one column-word gather per fragment, no per-row get_bit
                present.update(frag.rows_with_column(off))
            ids = sorted(set(ids) & present)

        prev, has_prev = call.uint_arg("previous")
        if has_prev:
            ids = [r for r in ids if r > prev]
        limit, has_limit = call.uint_arg("limit")
        if has_limit:
            ids = ids[:limit]
        return RowIdentifiers(rows=ids)

    def _rows_views(self, field: Field, call: Call) -> list[str] | None:
        """Time-bounded Rows: compute the view cover (reference
        executor.go:1342-1402)."""
        from_arg = call.args.get("from")
        to_arg = call.args.get("to")
        if from_arg is None and to_arg is None:
            return None
        cover = self._view_cover(field, from_arg, to_arg)
        return [] if cover is None else cover

    def _maybe_translate_col(self, idx: Index, col) -> int:
        if isinstance(col, str):
            if not idx.keys:
                raise ExecuteError("string column on unkeyed index")
            return self.translator.translate_key(idx.name, "", col)
        return int(col)

    # --------------------------------------------------------------- GroupBy

    def _groupby_plan(
        self, idx: Index, call: Call, shards: list[int], filt_row=_UNSET
    ):
        """A ``GroupBy`` validated and read (reference executor.go:1071-1100):
        ``(levels, filter row or None, limit or 0, previous or None)``,
        ``levels`` being ``[(field name, field, row ids)]``, one a ``Rows``
        child.  ``filt_row``: as :meth:`_execute_groupby` takes it."""
        if not call.children:
            raise ExecuteError("GroupBy requires at least one Rows() child")
        for c in call.children:
            if c.name != "Rows":
                raise ExecuteError("GroupBy children must be Rows queries")
        limit, has_limit = call.uint_arg("limit")
        filt_call, has_filt = call.call_arg("filter")
        previous, has_prev = call.uint_slice_arg("previous")
        if has_prev and len(previous) != len(call.children):
            raise ExecuteError(
                "'previous' argument must have a value for each GroupBy field"
            )
        if filt_row is _UNSET:
            filt_row = (
                self._bitmap_call(idx, filt_call, shards) if has_filt else None
            )
        levels = []
        for c in call.children:
            fname = c.args.get("_field")
            field = idx.field(fname)
            if field is None:
                raise FieldNotFoundError(f"field not found: {fname}")
            row_ids = self._execute_rows(idx, c, shards).rows
            levels.append((fname, field, row_ids))
        return (
            levels, filt_row, limit if has_limit and limit > 0 else 0,
            previous if has_prev else None,
        )

    @staticmethod
    def _groupby_batchable(levels, previous) -> bool:
        """Whether the batch paths take the call: no paging, two levels
        or more, every level with a standard view."""
        return previous is None and len(levels) >= 2 and all(
            f.view(VIEW_STANDARD) is not None for _, f, _ in levels
        )

    def _execute_groupby(
        self, idx: Index, call: Call, shards: list[int] | None,
        filt_row=_UNSET,
    ) -> list[GroupCount]:
        """reference executor.go:1071-1275: nested cross-product of Rows()
        children, each level intersected with the previous.  ``filt_row``
        lets the batched BSI lane hand in a precomputed filter row (its
        Range filter rode a shared range_batch launch); the _UNSET
        default computes it from the call as before.

        The per-call driver of the k-level steps: it resumes them to
        their end at once, every level awaited before the next is
        launched (the lane driver is :meth:`_groupby_lane`)."""
        shards = self._shards_for(idx, shards)
        levels, filt_row, limit, previous = self._groupby_plan(
            idx, call, shards, filt_row
        )
        fast = None
        if self._groupby_batchable(levels, previous):
            if len(levels) == 2 and filt_row is None:
                # Two-level fast path: the pair-count kernel needs no
                # prefix masks at all (reference executor.go:3208-3211).
                fast = self._groupby_two_level_batch(idx, levels, shards)
            else:
                # k-level: one batched intersect-count launch per level
                # over running prefix masks, pruning empty combos.
                steps = self._groupby_k_level_steps(levels, shards, filt_row)
                with tracing.start_span("executor.groupByKLevel").set_tag(
                    "levels", len(levels)
                ):
                    try:
                        while True:
                            next(steps)
                    except StopIteration as end:
                        fast = end.value
        if fast is None:
            return self._groupby_recursive(
                levels, shards, filt_row, previous, limit
            )
        return fast[:limit] if limit else fast

    def _groupby_recursive(
        self, levels, shards: list[int], filt_row, previous, limit: int
    ) -> list[GroupCount]:
        """The reference's own order of work, one intersection a
        combination: what answers a paged call, a call of one level, and
        whatever the batch paths decline."""
        results: list[GroupCount] = []
        has_prev = previous is not None
        # one device gather per (level, row), not per combination
        row_cache: dict[tuple[int, int], Row] = {}

        def level_row(level: int, rid: int) -> Row:
            key = (level, rid)
            if key not in row_cache:
                row_cache[key] = self._field_row(levels[level][1], rid, shards)
            return row_cache[key]

        def done() -> bool:
            return limit > 0 and len(results) >= limit

        def recurse(level: int, acc: Row | None, group: list[FieldRow], on_bound: bool):
            """Depth-first cross product in row order. ``on_bound`` tracks
            whether the prefix equals the `previous` page bound, in which
            case rows before the bound are skipped and the bound combo
            itself is excluded (reference executor.go:3127-3156 paging)."""
            if done():
                return
            fname, field, row_ids = levels[level]
            is_last = level + 1 == len(levels)
            for rid in row_ids:
                if done():
                    return
                bound_here = False
                if on_bound:
                    b = previous[level]
                    if rid < b:
                        continue
                    if rid == b:
                        if is_last:
                            continue  # strictly after the bound combo
                        bound_here = True
                row = level_row(level, rid)
                cur = row if acc is None else acc.intersect(row)
                g = group + [FieldRow(field=fname, row_id=rid)]
                if not is_last:
                    recurse(level + 1, cur, g, bound_here)
                else:
                    final = cur if filt_row is None else cur.intersect(filt_row)
                    cnt = final.count()
                    if cnt > 0:
                        results.append(GroupCount(group=g, count=cnt))

        recurse(0, None, [], has_prev)
        return results

    _GROUPBY_BATCH_MAX = 65536

    def _groupby_two_level_batch(
        self, idx: Index, levels, shards: list[int]
    ) -> list[GroupCount] | None:
        """All (row1, row2) combination counts in one launch; None when
        stacks are unavailable or the combo count is too large."""
        from pilosa_tpu.ops import kernels

        (f1name, f1, rows1), (f2name, f2, rows2) = levels
        n_combo = len(rows1) * len(rows2)
        if n_combo == 0:
            return []
        if n_combo > self._GROUPBY_BATCH_MAX:
            return None
        s1 = self.stacks.get(f1, shards)
        s2 = self.stacks.get(f2, shards) if f2 is not f1 else s1
        if s1 is None or s2 is None:
            return None
        slot1, bits1 = s1.slot_of, s1.bits
        slot2, bits2 = s2.slot_of, s2.bits
        present1 = [r for r in rows1 if r in slot1]
        present2 = [r for r in rows2 if r in slot2]
        if not present1 or not present2:
            return []
        with tracing.start_span("executor.groupByBatch").set_tag(
            "n", len(present1) * len(present2)
        ):
            # The full combination matrix is one cross-field gram scan on
            # the MXU (kernels.cross_gram_xla); the batched AND+popcount
            # kernels remain the fallback when the gram declines.
            counts2d = None
            if f2 is f1:
                uniq = sorted({slot1[r] for r in present1 + present2})
                g, pos = self.stacks.gram(f1, s1, bits1, uniq)
                if g is not None:
                    pa = np.array([pos[slot1[r]] for r in present1])
                    pb = np.array([pos[slot1[r]] for r in present2])
                    counts2d = g[np.ix_(pa, pb)]
            else:
                counts2d = self.stacks.cross_gram(
                    s1,
                    bits1,
                    s2,
                    bits2,
                    [slot1[r] for r in present1],
                    [slot2[r] for r in present2],
                )
            if counts2d is not None:
                counts = counts2d.reshape(-1)
            else:
                # wide pair batches (> GRAM_MAX_ROWS distinct rows):
                # local stacks return [B, S] partials; process-spanning
                # stacks return replicated int64[B] in-program psum
                # totals (kernels.py r05 — the fast lane no longer
                # declines across hosts)
                if not kernels.row_counts_supported(bits1) or (
                    f2 is not f1
                    and not kernels.row_counts_supported(bits2)
                ):
                    # spanning mesh too large even for the chunked
                    # psum: decline so the recursive path answers it
                    return None
                combos_s = [
                    (slot1[r1], slot2[r2])
                    for r1 in present1
                    for r2 in present2
                ]
                B = _pow2(len(combos_s))
                if B > len(combos_s):
                    # pow2 batch pad: padded vs useful per-shard partials
                    kernels.note_pad(
                        "pair_count",
                        B * bits1.shape[0] * 4,
                        len(combos_s) * bits1.shape[0] * 4,
                    )
                ras = np.zeros(B, dtype=np.int32)
                rbs = np.zeros(B, dtype=np.int32)
                for j, (sa, sb) in enumerate(combos_s):
                    ras[j], rbs[j] = sa, sb
                ras_d, rbs_d = kernels.h2d(ras), kernels.h2d(rbs)
                if f2 is f1:
                    partials = kernels.pair_count_batched(bits1, ras_d, rbs_d)
                else:
                    partials = kernels.pair_count_two_batched(
                        bits1, bits2, ras_d, rbs_d
                    )
                partials = kernels.pull(partials, "pair_count").astype(
                    np.int64
                )
                counts = (
                    partials if partials.ndim == 1
                    else partials.sum(axis=1)
                )
        out = []
        with tracing.start_span("executor.demux").set_tag("n", len(counts)):
            for j, (r1, r2) in enumerate(
                (r1, r2) for r1 in present1 for r2 in present2
            ):
                c = int(counts[j])
                if c > 0:
                    out.append(
                        GroupCount(
                            group=[
                                FieldRow(field=f1name, row_id=r1),
                                FieldRow(field=f2name, row_id=r2),
                            ],
                            count=c,
                        )
                    )
        return out

    @staticmethod
    def _row_to_shard_matrix(row: Row, shards: list[int], bits) -> np.ndarray:
        """A Row's per-shard segments as a dense ``uint32[S, W]`` matrix
        aligned to the shard axis of the stack ``bits`` (its padding and
        its order over a mesh: ``stacks.positions``); absent shards are
        zero."""
        from pilosa_tpu.ops import kernels

        filt = np.zeros((bits.shape[0], bits.shape[-1]), dtype=np.uint32)
        for si, s in stacks_mod.positions(shards, bits):
            seg = row.segments.get(s)
            if seg is not None:
                # a segment a device lane produced is a wait for the device
                filt[si] = kernels.pull(seg, "row_segment")
        return filt

    # prefix-mask memory ceiling for the k-level GroupBy batch: one
    # device's share of the masks (the shard axis they inherit from the
    # stack is split over the mesh), as stacks.STACK_BUDGET_BYTES is
    _GROUPBY_PREFIX_BUDGET_BYTES = 256 << 20
    # what the GroupBy lane's levels that are enqueued and not yet pulled
    # may hold between them, reckoned as _groupby_k_level_steps yields it
    # (one device's share again).  On a v5e the allocator's peak did not
    # move between 2 and 8 GiB (PERF.md, PR 41), and 2 GiB held back every
    # second level of a 12-shard flight
    _GROUPBY_LANE_BUDGET_BYTES = 8 << 30

    @staticmethod
    def _groupby_mask_bytes(bits) -> int:
        """One device's share of one ``[S, W]`` mask over the stack
        ``bits``."""
        from pilosa_tpu.ops import kernels

        layout = kernels.shards_axis_of(bits)
        n_dev = 1 if layout is None else layout[0].shape[layout[1]]
        S, _, W = bits.shape
        return S // n_dev * W * 4

    @classmethod
    def _groupby_prefix_max(cls, bits) -> int:
        """How many ``[S, W]`` prefix masks over the stack ``bits`` the
        budget admits."""
        return max(
            1, cls._GROUPBY_PREFIX_BUDGET_BYTES // cls._groupby_mask_bytes(bits)
        )

    def _groupby_k_level_steps(
        self, levels, shards: list[int], filt_row, deferred: bool = False
    ):
        """All k-level combination counts with O(1) launches per level:
        maintain [C, S, W] intersection masks for surviving combos, count
        every (combo, next-row) pair in one scan launch, prune zeros,
        refine. Matches reference semantics executor.go:3057-3230
        (DFS row order, count = intersection of all levels + filter).

        A generator that stops where it would wait, twice a level: it
        yields the bytes the level's count is about to hold on a device
        (``C`` prefix masks and ``Rl`` gathered rows) and enqueues the
        launch when resumed; it yields None with the launch enqueued and
        pulls when resumed.  Its driver chooses when: at once
        (:meth:`_execute_groupby`), or once its flight-mates' levels are
        enqueued too (:meth:`_groupby_lane`, which passes ``deferred``:
        ``kernels.combo_counts_gram``).  It returns the groups, or
        None when stacks are unavailable or the surviving combo set would
        exceed the prefix budget (the recursive path then answers)."""
        from pilosa_tpu.ops import kernels

        stacks = []
        for _, f, _ in levels:
            st = self.stacks.get(f, shards)
            if st is None:
                return None
            stacks.append((st.slot_of, st.bits))
        slot0, bits0 = stacks[0]
        if kernels.stack_spans_processes(bits0):
            # combo-count kernels return per-shard partials, not host
            # addressable on a spanning stack; recursive path serves
            return None
        cmax = self._groupby_prefix_max(bits0)
        mask_bytes = self._groupby_mask_bytes(bits0)

        rows1 = [r for r in levels[0][2] if r in slot0]
        if not rows1:
            return []
        if len(rows1) > cmax or len(rows1) > self._GROUPBY_BATCH_MAX:
            return None
        prefix = kernels.gather_prefix(
            bits0, kernels.h2d([slot0[r] for r in rows1], dtype=np.int32)
        )
        if filt_row is not None:
            filt = self._row_to_shard_matrix(filt_row, shards, bits0)
            prefix = prefix & kernels.h2d(filt)[None]
        combos: list[tuple[int, ...]] = [(r,) for r in rows1]

        for li in range(1, len(levels)):
            slotL, bitsL = stacks[li]
            rows = [r for r in levels[li][2] if r in slotL]
            if not rows:
                return []
            yield (len(combos) + len(rows)) * mask_bytes
            idxL = kernels.h2d([slotL[r] for r in rows], dtype=np.int32)
            # MXU cross gram when safe (one prefix read per level);
            # per-shard scan partials otherwise
            launched = kernels.combo_counts_gram(prefix, bitsL, idxL, deferred)
            gram = launched is not None
            if not gram:
                launched = kernels.combo_counts(prefix, bitsL, idxL)
            yield None
            counts = kernels.pull(
                launched, "combo_gram" if gram else "combo_counts"
            ).astype(np.int64)
            if not gram:
                counts = counts.sum(axis=2)  # per-shard partials -> [C, Rl]
            live = np.argwhere(counts > 0)  # row-major: DFS order
            if li == len(levels) - 1:
                with tracing.start_span("executor.demux").set_tag(
                    "n", len(live)
                ):
                    return [
                        GroupCount(
                            group=[
                                FieldRow(field=levels[k][0], row_id=rid)
                                for k, rid in enumerate(
                                    combos[ci] + (rows[ri],)
                                )
                            ],
                            count=int(counts[ci, ri]),
                        )
                        for ci, ri in live
                    ]
            if len(live) == 0:
                return []
            if len(live) > cmax or len(live) > self._GROUPBY_BATCH_MAX:
                return None
            prefix = kernels.refine_prefix(
                prefix,
                bitsL,
                kernels.h2d(live[:, 0], dtype=np.int32),
                kernels.h2d(
                    [slotL[rows[ri]] for ri in live[:, 1]], dtype=np.int32
                ),
            )
            combos = [combos[ci] + (rows[ri],) for ci, ri in live]
        return []

    # --------------------------------------------------------------- Options

    def _execute_options(self, idx: Index, call: Call, shards: list[int] | None) -> Any:
        """reference executor.go:344-406 executeOptionsCall."""
        if len(call.children) != 1:
            raise ExecuteError("Options() requires exactly one child")
        exclude_columns, _ = call.bool_arg("excludeColumns")
        exclude_row_attrs, _ = call.bool_arg("excludeRowAttrs")
        column_attrs, _ = call.bool_arg("columnAttrs")
        shards_arg, has_shards = call.uint_slice_arg("shards")
        if has_shards:
            shards = shards_arg
        result = self._execute_call(idx, call.children[0], shards)
        if isinstance(result, Row):
            if exclude_columns:
                result.segments = {}
            if exclude_row_attrs:
                result.attrs = {}
            if column_attrs:
                result.attrs["columnattrs"] = [
                    {"id": int(c), "attrs": idx.column_attrs.attrs(int(c))}
                    for c in result.columns()
                    if idx.column_attrs.attrs(int(c))
                ]
        return result
