"""General PQL-AST -> one-launch compiler over serving field stacks.

The reference executes arbitrary bitmap trees per shard inside its worker
pool (executor.go:653-680 executeBitmapCallShard recursing over
Row/Intersect/Union/Difference/Xor/Not).  The TPU analogue traces the
SAME tree once into a single XLA program over the cached ``[S, R, W]``
field stacks (SURVEY §7: "PQL AST -> traced JAX computation, one XLA
program per query shape, cached"):

* The program is cached by the AST's *shape* — the operator tree plus
  which field each leaf reads — never by row ids.  Row ids arrive as an
  ``int32`` slots input, so ``Count(Intersect(Row(f=1), Row(f=2)))`` and
  ``Count(Intersect(Row(f=7), Row(f=9)))`` share one compiled program,
  and a batch of same-shape Counts runs as ONE launch via an on-device
  scan over the slot rows.
* Absent rows ride through as slot ``-1``: the leaf gathers row 0 and
  masks it to zero words, which is exactly the empty-row semantics of
  every operator (including Not/Difference).
* ``Not`` is rewritten at match time into
  ``Difference(Row(_exists=0), child)`` — the reference's executeNot
  (executor.go) against the existence field, as a plain tree node.
* A time-range ``Row(f=v, from=..., to=...)`` expands into a Union of
  per-view leaves over the minimal time-view cover (reference
  executor.go:1515-1531; the reference treats time views as ordinary
  fragments, view.go:33-38) — so time-quantum queries ride the same
  compiled one-launch programs, with one cached stack per (field, view).
* Under a ``Sum`` (:func:`match_sum_filter`) a pure BSI condition is a
  leaf too: it names its int field's raw stack and carries its encoded
  bounds as per-query input beside the row slots, and the tree compiles
  into the Sum's own program (:func:`run_sum_batch`): the filter words
  never exist on the host.

Launches are counted in :data:`launches` so tests can assert O(1)
dispatch per query batch regardless of shard count or tree width.
"""

from __future__ import annotations

from functools import lru_cache
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from pilosa_tpu.core import timequantum
from pilosa_tpu.core.field import FIELD_TYPE_INT
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.exec import planner as planner_mod
from pilosa_tpu.obs import devledger
from pilosa_tpu.ops import bsi
from pilosa_tpu.pql.ast import Call, Condition

# Device cost ledger site for compiled-plan launches: every run_* call
# opens a launch window so XLA compiles (new AST shape or batch bucket)
# attribute here, and the compiled-callable identity feeds cache-hit
# accounting.
_DL = devledger.site("exec.astbatch")

# Device launches issued by compiled programs (tests assert O(1) per
# batch; one count-group launch answers every same-shape Count).
launches = 0

_OPS = {
    "Intersect": "intersect",
    "Union": "union",
    "Difference": "difference",
    "Xor": "xor",
}

# Largest time-view cover a range leaf may expand to: past this, the
# per-view stack builds and the unrolled leaf gathers cost more than the
# segment path's plain union (a fine quantum over a wide window can
# cover thousands of views).
MAX_TIME_COVER = 16

# sig nodes: ("row", stack_ordinal) | ("range", stack_ordinal, depth) |
# (op, *child_sigs).  Leaves refer
# to (field, view) stacks by first-appearance ORDINAL, not by name: the
# compiled program depends only on the tree shape and stack positions,
# so a rolling time window (same cover shape, different view names)
# reuses one program instead of tracing a fresh one per period.  The
# actual (field, view) pairs ride alongside in ``pairs`` and join the
# executor's launch-group key.  A "range" leaf (a Sum's filter only)
# reads the raw BSI stack of an int field, ``depth`` planes deep.


def _stackable_field(idx, fname: str):
    """The field when it can serve stacked reads at all (per-view
    existence is checked by the stack builder; an absent view is an
    all-zero leaf)."""
    if fname is None:
        return None
    field = idx.field(fname)
    if field is None or field.field_type == FIELD_TYPE_INT:
        return None
    return field


def _ordinal(pairs: list[tuple[str, str]], fname: str, vname: str) -> int:
    pair = (fname, vname)
    try:
        return pairs.index(pair)
    except ValueError:
        pairs.append(pair)
        return len(pairs) - 1


def _shape(call: Call) -> str:
    """A sort key that tells a tree's shape and fields and none of its
    drawn values, so both orders of a commutative node's children sign
    into one program."""
    if call.children:
        return f"{call.name}({','.join(sorted(map(_shape, call.children)))})"
    fname = call.field_arg() or ""
    ranged = isinstance(call.args.get(fname), Condition)
    timed = "from" in call.args or "to" in call.args
    return f"{call.name}:{fname}:{ranged:d}{timed:d}"


def match_tree(
    idx,
    call: Call,
    leaves: list[tuple[str, str, int]],
    pairs: list[tuple[str, str]],
    ranges: list | None = None,
):
    """``sig`` for a batchable bitmap tree, appending its
    (field, view, row) leaves in traversal order and the distinct
    (field, view) stack pairs to ``pairs`` (the compiled program's
    argument order); None when any node falls outside the compilable set
    (BSI conditions, Shift, keyed rows...).

    ``ranges`` is a Sum's filter (:func:`match_sum_filter`) and no other
    caller's: a pure BSI condition then signs as a "range" leaf, its
    ``(field, condition)`` appended to ``ranges`` in traversal order and
    its int field's BSI view to ``pairs``, and the children of a
    commutative node sign in the order of their shapes.  Without it a
    tree signs as it always did."""
    name = call.name
    if name == planner_mod.SHARED:
        # flight-planner graft (exec/planner.py): the subtree is already
        # a materialized host row.  Declining the compiled path here is
        # the POINT of the graft — the consumer combines it with cheap
        # host segment algebra instead of re-launching the whole tree.
        # (Any unknown name declines anyway; this spells the contract.)
        return None
    if ranges is not None:
        m = _bsi_condition(idx, call)
        if m is not None:
            field = m[0]
            ranges.append(m)
            return (
                "range", _ordinal(pairs, field.name, field.bsi_view_name()),
                field.bit_depth,
            )
    if name == "Row":
        fname = call.field_arg()
        field = _stackable_field(idx, fname)
        if field is None or call.children:
            return None
        v = call.args.get(fname)
        if not isinstance(v, int) or isinstance(v, bool):
            return None
        if "from" in call.args or "to" in call.args:
            # time range -> Union over the minimal view cover
            if set(call.args) - {fname, "from", "to"}:
                return None
            try:
                cover = timequantum.view_cover(
                    field, call.args.get("from"), call.args.get("to"),
                    VIEW_STANDARD,
                )
            except ValueError:
                return None
            if not cover or len(cover) > MAX_TIME_COVER:
                # empty range (segment path is free) or a cover so wide
                # that unrolled leaves/stacks would cost more than the
                # per-fragment union
                return None
            for vname in cover:
                leaves.append((fname, vname, v))
            return (
                "union",
                *[("row", _ordinal(pairs, fname, vn)) for vn in cover],
            )
        if set(call.args) != {fname}:
            return None
        if field.view(VIEW_STANDARD) is None:
            return None
        leaves.append((fname, VIEW_STANDARD, v))
        return ("row", _ordinal(pairs, fname, VIEW_STANDARD))
    if name == "Not":
        # executeNot: exists-row difference (requires track_existence)
        if len(call.children) != 1 or call.args or not idx.track_existence:
            return None
        ef = idx.existence_field()
        if ef is None or ef.view(VIEW_STANDARD) is None:
            return None
        leaves.append((ef.name, VIEW_STANDARD, 0))
        esig = ("row", _ordinal(pairs, ef.name, VIEW_STANDARD))
        child = match_tree(idx, call.children[0], leaves, pairs, ranges)
        if child is None:
            return None
        return ("difference", esig, child)
    op = _OPS.get(name)
    if op is not None:
        if not call.children or call.args:
            return None
        children = call.children
        if ranges is not None and name in planner_mod.COMMUTATIVE:
            children = sorted(children, key=_shape)
        subs = []
        for c in children:
            s = match_tree(idx, c, leaves, pairs, ranges)
            if s is None:
                return None
            subs.append(s)
        return (op, *subs)
    return None


def match_count(
    idx,
    call: Call,
    leaves: list[tuple[str, str, int]],
    pairs: list[tuple[str, str]],
):
    """sig for ``Count(tree)`` when the tree is compilable and not a bare
    Row (plain row counts are already one gather on the segment path)."""
    if call.name != "Count" or len(call.children) != 1 or call.args:
        return None
    child = call.children[0]
    if child.name == "Row":
        return None
    return match_tree(idx, child, leaves, pairs)


def _build(sig, ctr: list[int]):
    """Recursively build the tree evaluator: (stacks, slots[, bounds]) ->
    [S, W] words.  Leaf order mirrors match_tree's traversal order;
    ``ctr`` counts the row leaves and the range leaves met so far.
    ``bounds`` holds, for each range leaf in that order, one query's
    row of ``bsi.pack_bounds``; a tree without one reads none."""
    if sig[0] == "row":
        li = ctr[0]
        ctr[0] += 1
        fi = sig[1]

        def leaf(stacks, slots, bounds=(), li=li, fi=fi):
            s = slots[li]
            row = stacks[fi][:, jnp.maximum(s, 0)]  # [S, W]
            return row & jnp.where(
                s >= 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)
            )

        return leaf
    if sig[0] == "range":
        ri = ctr[1]
        ctr[1] += 1
        _, fi, depth = sig

        def span(stacks, slots, bounds):
            return bsi.range_words(stacks[fi], bounds[ri], depth=depth)

        return span
    op = sig[0]
    kids = [_build(k, ctr) for k in sig[1:]]

    if op == "difference":
        if len(kids) == 1:
            return kids[0]

        # left fold a\b\c == a & ~(b | c) (reference row.go Difference)
        def node(stacks, slots, bounds=()):
            rest = kids[1](stacks, slots, bounds)
            for k in kids[2:]:
                rest = rest | k(stacks, slots, bounds)
            return kids[0](stacks, slots, bounds) & ~rest

        return node

    fold = {"intersect": lambda a, b: a & b, "union": lambda a, b: a | b,
            "xor": lambda a, b: a ^ b}[op]

    def node(stacks, slots, bounds=()):
        out = kids[0](stacks, slots, bounds)
        for k in kids[1:]:
            out = fold(out, k(stacks, slots, bounds))
        return out

    return node


def _count_scan(root, stacks, slots_b):
    """int32 [B, S] per-shard counts for a slot batch: on-device scan
    over the batch, no [B, S, W] materialization.  Shared by the local
    and spanning compiled programs so count semantics cannot diverge."""

    def body(_, sl):
        words = root(stacks, sl)
        return None, jnp.sum(
            lax.population_count(words).astype(jnp.int32), axis=-1
        )

    _, counts = lax.scan(body, None, slots_b)
    return counts


@lru_cache(maxsize=256)
def compiled(sig, count_mode: bool):
    """(jitted_fn, n_leaves) for an AST shape.  ``count_mode`` programs
    take ``(stacks, slots[B, L])`` and return int32 ``[B, S]`` per-shard
    counts (scan over the batch — no [B, S, W] materialization); bitmap
    programs take ``(stacks, slots[L])`` and return the uint32 ``[S, W]``
    result words."""
    ctr = [0, 0]
    root = _build(sig, ctr)
    n_leaves = ctr[0]

    if count_mode:

        @jax.jit
        def run(stacks, slots_b):
            return _count_scan(root, stacks, slots_b)  # [B, S]

    else:

        @jax.jit
        def run(stacks, slots):
            return root(stacks, slots)  # [S, W]

    return run, n_leaves


@lru_cache(maxsize=256)
def _compiled_spanning(sig, mesh, axis, chunk, n_stacks):
    """jit(shard_map) count-batch program for a PROCESS-SPANNING mesh:
    per-shard partials are not host addressable there, so each device
    evaluates the tree over its local shard block in ``chunk``-shard
    slices and the reduce is an in-program chunked psum with (hi, lo)
    uint32 carry-save (exact past int32 — the same machinery as
    ops/kernels.py's spanning pair/gram kinds).  Returns replicated
    (hi, lo) uint32[B] arrays."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from pilosa_tpu.ops import kernels as _k

    ctr = [0, 0]
    root = _build(sig, ctr)
    n_leaves = ctr[0]

    def local(*args):
        *stks, slots_b = args

        def part(*blks):
            # [B, S_chunk] -> [B] int32, chunk-bounded by construction
            return _count_scan(root, tuple(blks), slots_b).sum(axis=1)

        return _k._carry_psum_chunks(part, tuple(stks), axis, chunk)

    in_specs = tuple(P(axis, None, None) for _ in range(n_stacks)) + (
        P(None),
    )
    fn = jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(None), P(None)),
            check_vma=False,
        )
    )
    return fn, n_leaves


def run_count_batch(sig, stacks: tuple, slots_np: np.ndarray) -> np.ndarray:
    """One launch: int64 totals for a batch of same-shape Counts.
    ``slots_np`` is int32 [B, L] (pad rows with -1 slots are fine — they
    count zero and callers slice them off).  Local stacks sum [B, S]
    partials host-side; process-spanning stacks reduce in-program and
    raise ValueError only when totals could exceed int32 even per
    single-shard psum slice (the row_counts contract)."""
    global launches
    from pilosa_tpu.ops import kernels as _k

    m = _k.shards_axis_of(stacks[0])
    if m is not None and _k.mesh_spans_processes(m[0]):
        mesh, axis = m
        W = stacks[0].shape[2]
        chunk = _k._psum_chunk_size(mesh, W)
        if chunk < 1:
            raise ValueError(
                "AST count totals exceed int32 even per single psum"
                " slice; shrink the shard width or the per-host mesh"
            )
        fn, n_leaves = _compiled_spanning(
            sig, mesh, axis, chunk, len(stacks)
        )
        assert slots_np.shape[1] == n_leaves
        launches += 1
        label = f"count_span B{slots_np.shape[0]} S{stacks[0].shape[0]}"
        _DL.track(fn, (slots_np.shape, stacks[0].shape))
        with _DL.launch(sig=label) as w:
            w.mesh = True
            hi, lo = fn(*stacks, jnp.asarray(slots_np))
        return _k._hi_lo_total(hi, lo)
    fn, n_leaves = compiled(sig, True)
    assert slots_np.shape[1] == n_leaves
    launches += 1
    label = f"count B{slots_np.shape[0]} S{stacks[0].shape[0]}"
    _DL.track(fn, (slots_np.shape, tuple(s.shape for s in stacks)))
    slots = _k.h2d(slots_np)
    with _DL.launch(sig=label) as w:
        w.mesh = _k._multi_device(stacks[0])
        with _k.enqueue("ast_count"):
            out = fn(stacks, slots)
        partials = _k.pull(out, "ast_count").astype(np.int64)
    if w.compiles:
        devledger.ledger().analyze_cost(
            _DL, fn, stacks, jnp.asarray(slots_np), sig=label
        )
    return partials.sum(axis=1)


def run_bitmap(sig, stacks: tuple, slots_np: np.ndarray):
    """One launch: the uint32 [S, W] result words of a bitmap tree."""
    global launches
    fn, n_leaves = compiled(sig, False)
    assert slots_np.shape[0] == n_leaves
    launches += 1
    _DL.track(fn, tuple(s.shape for s in stacks))
    from pilosa_tpu.ops import kernels as _k

    slots = _k.h2d(slots_np)
    with _DL.launch(sig=f"bitmap S{stacks[0].shape[0]}") as w, _k.enqueue(
        "ast_bitmap"
    ):
        w.mesh = _k._multi_device(stacks[0])
        return fn(stacks, slots)


# ------------------------------------------------- a filtered Sum's program
#
# ``Sum(<tree>, field=v)``: the tree compiles into the Sum's own program.
# One launch takes the summed field's RAW stack, the stacks the filter's
# leaves read, a chunk of queries' row slots and encoded range bounds;
# it builds each query's filter words where the stacks lie and feeds
# them to the fused popcount matmul (ops/bsi.py).  Nothing but slots and
# bounds leaves the host, and only the accumulator comes back.

# queries a launch: a flight's same-shape Sums go in chunks of this many,
# the last padded with queries that select nothing (slot -1, zero
# bounds), so a shape compiles ONE program whatever a flight holds (a
# bucket a size would be a program a bucket the warm-up has to meet).
# 4, not 8: ssb-flat's flights hold 2.3 Sums a (field, shape) group, and
# at the same seed the device spent 21.6 ms a read against 22.0 (PERF.md
# section 6, PR 43); a larger group takes one more launch a four
SUM_CHUNK = 4


def match_sum_filter(idx, call: Call):
    """``(sig, pairs, leaves, ranges)`` of a ``Sum``'s filter tree as its
    own program compiles it (:func:`match_tree` with range leaves), or
    None: the Sum is then the per-call path's."""
    leaves: list[tuple[str, str, int]] = []
    pairs: list[tuple[str, str]] = []
    ranges: list = []
    sig = match_tree(idx, call, leaves, pairs, ranges)
    if sig is None:
        return None
    return sig, tuple(pairs), leaves, ranges


def _range_depths(sig) -> list[int]:
    """The depths of a sig's range leaves, in traversal order."""
    if sig[0] == "range":
        return [sig[2]]
    if sig[0] == "row":
        return []
    return [d for k in sig[1:] for d in _range_depths(k)]


@lru_cache(maxsize=256)
def compiled_sum(sig, layout=None):
    """(jitted_fn, row leaves) of a filtered Sum whose filter signs as
    ``sig``: ``(bits, stacks, queries)`` -> the accumulator
    ``int32[depth+1, 2P]`` of ``bsi.sum_filters_acc``.  ``queries`` is
    ``uint32[P, ...]``, a row a query: its row slots (int32 bits), then
    its ``bsi.pack_bounds`` row of each range leaf.  A scan over the
    queries builds the filter words ``[P, S, W]``.  ``layout`` = (mesh,
    axis) of stacks sharded over a serving mesh: each device then runs
    the same on its own shards, no collective, and the accumulators come
    back ``[devices, depth+1, 2P]`` along the mesh axis; the queries are
    replicated."""
    ctr = [0, 0]
    root = _build(sig, ctr)
    n_leaves, depths = ctr[0], _range_depths(sig)

    def local(bits, stacks, queries):
        def one(_, q):
            slots = lax.bitcast_convert_type(q[:n_leaves], jnp.int32)
            bounds, at = [], n_leaves
            for d in depths:
                bounds.append(q[at:at + bsi.bounds_width(d)])
                at += bsi.bounds_width(d)
            return None, root(stacks, slots, bounds)

        _, words = lax.scan(one, None, queries)
        return bsi.sum_filters_acc(bits, jnp.swapaxes(words, 0, 1))

    if layout is None:
        return jax.jit(local), n_leaves
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh, axis = layout
    stack_spec = P(axis, None, None)
    fn = jax.jit(
        shard_map(
            lambda *a: local(*a)[None],
            mesh=mesh,
            in_specs=(stack_spec, stack_spec, P(None)),
            out_specs=stack_spec,
            check_vma=False,
        )
    )
    return fn, n_leaves


def run_sum_batch(
    sig, bits, stacks: tuple, slots_np, bounds_np: tuple, n: int
):
    """One launch, NOT awaited: the device accumulator of a chunk of
    same-shape filtered Sums, the first ``n`` of ``SUM_CHUNK`` queries,
    over the summed field's raw stack ``bits`` (``bsi.sum_pairs`` reads
    it once pulled).  ``slots_np`` is int32 ``[SUM_CHUNK, row leaves]``
    and ``bounds_np`` one ``bsi.pack_bounds`` array a range leaf, in
    traversal order: they go up as ONE array.  ``stacks`` in ``pairs``
    order, all laid out as ``bits`` is."""
    global launches
    from pilosa_tpu.ops import kernels as _k

    fn, n_leaves = compiled_sum(sig, _k.shards_axis_of(bits))
    assert slots_np.shape[1] == n_leaves
    launches += 1
    queries = _k.h2d(
        np.concatenate([slots_np.view(np.uint32), *bounds_np], axis=1)
    )
    with _k.enqueue("bsi_sum_filtered") as sp:
        acc = fn(bits, stacks, queries)
    _k.note_bsi_dispatch(
        "bsi_sum_filtered",
        wall=sp.duration,
        args=(bits, queries, *stacks),
        depth=int(bits.shape[1]) - 2,
        q_bucket=int(slots_np.shape[0]),
        q_useful=n,
    )
    return acc


# ------------------------------------------------------------- BSI signing
#
# BSI op classes the executor's cross-request batch lane understands
# (executor._batch_bsi).  A signed call joins a (field, depth, op-class)
# flight group and is answered by ONE shared slice-plane launch per group
# (ops/bsi.py batched kernels).  The dispatch-parity graftlint pass
# (part C) checks this class list against the executor's handlers, so a
# class signed here but never grouped there is a CI failure.

BSI_RANGE = "bsi.range"
BSI_RANGE_COUNT = "bsi.range_count"
BSI_RANGE_COUNT_FILTERED = "bsi.range_count_filtered"
BSI_SUM = "bsi.sum"
BSI_MIN = "bsi.min"
BSI_MAX = "bsi.max"
BSI_GROUPBY = "bsi.groupby"

BSI_OP_CLASSES = (
    BSI_RANGE, BSI_RANGE_COUNT, BSI_RANGE_COUNT_FILTERED, BSI_SUM, BSI_MIN,
    BSI_MAX, BSI_GROUPBY,
)


def _bsi_condition(idx, call: Call):
    """(field, Condition) when ``call`` is a pure BSI range predicate —
    ``Row(v < 3)`` / ``Range(v < 3)`` over an int field; None otherwise.
    ``== null`` is left unsigned so the per-call path raises it inside
    the owning query's demux scope."""
    if call.name not in ("Row", "Range") or call.children:
        return None
    fname = call.field_arg()
    if fname is None or set(call.args) != {fname}:
        return None
    field = idx.field(fname)
    if field is None or not field.is_bsi():
        return None
    cond = call.args.get(fname)
    if not isinstance(cond, Condition):
        return None
    if cond.op == "==" and cond.value is None:
        return None
    return field, cond


def _filtered_condition(idx, call: Call):
    """(field, Condition, leaves) when ``call`` is an ``Intersect`` of
    exactly one pure BSI condition and one or more plain set rows, its
    children in any order (the planner reorders commutative children
    before the lanes run); None otherwise.  ``leaves`` are the rows'
    ``(field, view, row)`` as :func:`match_tree` accepts a ``Row`` leaf,
    sorted, so both child orders sign into one group."""
    if call.name != "Intersect" or call.args or len(call.children) < 2:
        return None
    found = None
    leaves: list[tuple[str, str, int]] = []
    for c in call.children:
        m = _bsi_condition(idx, c)
        if m is not None:
            if found is not None:
                return None
            found = m
            continue
        # a time-range leaf signs as a union and a graft as nothing
        sig = match_tree(idx, c, leaves, []) if c.name == "Row" else None
        if sig is None or sig[0] != "row":
            return None
    if found is None or not leaves:
        return None
    return found[0], found[1], tuple(sorted(leaves))


def match_bsi(idx, call: Call):
    """Sign one call as BSI-batchable: ``(op_class, field, condition,
    leaves)`` (condition None for the aggregate classes, which carry
    their filter as a child/arg instead; ``leaves`` the set rows a
    filtered range count intersects with, empty for every other class)
    or None.  Conservative by construction — anything unsigned keeps the
    exact per-call semantics."""
    name = call.name
    m = _bsi_condition(idx, call)
    if m is not None:
        return BSI_RANGE, m[0], m[1], ()
    if name == "Count" and len(call.children) == 1 and not call.args:
        m = _bsi_condition(idx, call.children[0])
        if m is not None:
            return BSI_RANGE_COUNT, m[0], m[1], ()
        m = _filtered_condition(idx, call.children[0])
        if m is not None:
            return BSI_RANGE_COUNT_FILTERED, *m
        return None
    if name in ("Sum", "Min", "Max"):
        fname, ok = call.string_arg("field")
        if not ok:
            fname = call.args.get("_field")
        field = idx.field(fname) if fname else None
        if field is None or not field.is_bsi():
            return None
        cls = {"Sum": BSI_SUM, "Min": BSI_MIN, "Max": BSI_MAX}[name]
        return cls, field, None, ()
    if name == "GroupBy":
        filt, has = call.call_arg("filter")
        if has and filt is not None:
            m = _bsi_condition(idx, filt)
            if m is not None:
                return BSI_GROUPBY, m[0], m[1], ()
    return None
