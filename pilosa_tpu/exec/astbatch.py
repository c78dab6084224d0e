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

# sig nodes: ("row", stack_ordinal) | (op, *child_sigs).  Leaves refer
# to (field, view) stacks by first-appearance ORDINAL, not by name: the
# compiled program depends only on the tree shape and stack positions,
# so a rolling time window (same cover shape, different view names)
# reuses one program instead of tracing a fresh one per period.  The
# actual (field, view) pairs ride alongside in ``pairs`` and join the
# executor's launch-group key.


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


def match_tree(
    idx,
    call: Call,
    leaves: list[tuple[str, str, int]],
    pairs: list[tuple[str, str]],
):
    """``sig`` for a batchable bitmap tree, appending its
    (field, view, row) leaves in traversal order and the distinct
    (field, view) stack pairs to ``pairs`` (the compiled program's
    argument order); None when any node falls outside the compilable set
    (BSI conditions, Shift, keyed rows...)."""
    name = call.name
    if name == planner_mod.SHARED:
        # flight-planner graft (exec/planner.py): the subtree is already
        # a materialized host row.  Declining the compiled path here is
        # the POINT of the graft — the consumer combines it with cheap
        # host segment algebra instead of re-launching the whole tree.
        # (Any unknown name declines anyway; this spells the contract.)
        return None
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
        child = match_tree(idx, call.children[0], leaves, pairs)
        if child is None:
            return None
        return ("difference", esig, child)
    op = _OPS.get(name)
    if op is not None:
        if not call.children or call.args:
            return None
        subs = []
        for c in call.children:
            s = match_tree(idx, c, leaves, pairs)
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
    """Recursively build the tree evaluator: (stacks, slots) -> [S, W]
    words.  Leaf order mirrors match_tree's traversal order."""
    if sig[0] == "row":
        li = ctr[0]
        ctr[0] += 1
        fi = sig[1]

        def leaf(stacks, slots, li=li, fi=fi):
            s = slots[li]
            row = stacks[fi][:, jnp.maximum(s, 0)]  # [S, W]
            return row & jnp.where(
                s >= 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0)
            )

        return leaf
    op = sig[0]
    kids = [_build(k, ctr) for k in sig[1:]]

    if op == "difference":
        if len(kids) == 1:
            return kids[0]

        # left fold a\b\c == a & ~(b | c) (reference row.go Difference)
        def node(stacks, slots):
            rest = kids[1](stacks, slots)
            for k in kids[2:]:
                rest = rest | k(stacks, slots)
            return kids[0](stacks, slots) & ~rest

        return node

    fold = {"intersect": lambda a, b: a & b, "union": lambda a, b: a | b,
            "xor": lambda a, b: a ^ b}[op]

    def node(stacks, slots):
        out = kids[0](stacks, slots)
        for k in kids[1:]:
            out = fold(out, k(stacks, slots))
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
    ctr = [0]
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

    ctr = [0]
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
