"""Query-batched BSI kernel tests: one launch over stacked per-query
bounds must match numpy brute force AND the single-query kernels bit for
bit, across sign/negative-bound/out-of-band/depth-edge cases."""

import numpy as np
import pytest

from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.ops import bitops, bsi

DEPTH = 10
S = 3  # stacked shard axis


def _make_shard(rng, depth=DEPTH):
    cols = np.unique(rng.integers(0, 4000, size=200))
    lim = 1 << depth
    vals = rng.integers(-(lim - 1), lim, size=len(cols))
    values = dict(zip(cols.tolist(), vals.tolist()))
    f = Fragment()
    f.import_values(
        np.array(list(values), np.int64),
        np.array(list(values.values()), np.int64),
        depth,
    )
    return values, f


@pytest.fixture(scope="module")
def stacked():
    rng = np.random.default_rng(11)
    shard_values = []
    planes, exists, sign = [], [], []
    for _ in range(S):
        values, frag = _make_shard(rng)
        p, e, sg = frag.bsi_tensors(DEPTH)
        shard_values.append(values)
        planes.append(np.asarray(p))
        exists.append(np.asarray(e))
        sign.append(np.asarray(sg))
    return (
        shard_values,
        np.stack(planes),
        np.stack(exists),
        np.stack(sign),
    )


def _cols(words) -> set[int]:
    return set(bitops.unpack_columns(np.asarray(words)).tolist())


def _np_match(values: dict[int, int], op: str, value) -> set[int]:
    if op == "!=" and value is None:
        return set(values)
    if op == "><":
        lo, hi = value
        return {c for c, v in values.items() if lo <= v <= hi}
    if "x" in op:
        lo_op, hi_op = op.split("x")
        lo, hi = value
        return {
            c
            for c, v in values.items()
            if (v >= lo if lo_op == "<=" else v > lo)
            and (v <= hi if hi_op == "<=" else v < hi)
        }
    cmp = {
        "<": lambda v: v < value,
        "<=": lambda v: v <= value,
        ">": lambda v: v > value,
        ">=": lambda v: v >= value,
        "==": lambda v: v == value,
        "!=": lambda v: v != value,
    }[op]
    return {c for c, v in values.items() if cmp(v)}


# every op class x bounds hitting sign flips, zero, the depth edge
# (+/-1023), and out-of-band magnitudes (|v| >= 2^depth)
_QUERIES = [
    ("<", 37),
    ("<", -37),
    ("<=", 0),
    ("<", 0),
    (">", -1),
    (">=", 1023),
    ("<", -1023),
    (">", 1024),       # oob: nothing greater
    ("<", 5000),       # oob: everything smaller
    ("<=", -1024),     # oob negative: nothing
    (">=", -5000),     # oob negative: everything
    ("==", 12),
    ("==", -12),
    ("==", 4096),      # oob: empty
    ("!=", 0),
    ("!=", -7),
    ("!=", None),      # not-null
    ("><", (-100, 100)),
    ("><", (5, 4)),    # inverted: empty
    ("<x<", (-50, 50)),
    ("<=x<", (0, 1)),
    ("<x<=", (-1024, 1023)),
    ("<=x<=", (-3, 3)),
]


def _encode(queries):
    return [bsi.condition_bounds(op, v) for op, v in queries]


def test_range_batch_matches_numpy(stacked):
    shard_values, planes, exists, sign = stacked
    masks = np.asarray(
        bsi.range_batch(planes, exists, sign, _encode(_QUERIES), depth=DEPTH)
    )
    assert masks.shape[0] == bitops.pow2_pad_len(len(_QUERIES))
    for qi, (op, v) in enumerate(_QUERIES):
        for si, values in enumerate(shard_values):
            got = _cols(masks[qi, si])
            want = _np_match(values, op, v)
            assert got == want, (op, v, si)


def test_range_batch_matches_single_query_kernels(stacked):
    """The batched program and the per-op single-query programs must be
    bitwise identical — they compile differently but answer the same
    predicate."""
    _, planes, exists, sign = stacked
    masks = np.asarray(
        bsi.range_batch(planes, exists, sign, _encode(_QUERIES), depth=DEPTH)
    )
    for qi, (op, v) in enumerate(_QUERIES):
        if op in ("<", "<=", ">", ">="):
            fn = bsi.range_lt if op[0] == "<" else bsi.range_gt
            single = fn(
                planes, exists, sign,
                value=v, depth=DEPTH, allow_eq=op.endswith("="),
            )
        elif op == "==":
            single = bsi.range_eq(
                planes, exists, sign,
                value_abs=abs(v), negative=v < 0, depth=DEPTH,
            )
        else:
            continue
        assert np.array_equal(masks[qi], np.asarray(single)), (op, v)


def test_range_count_batch(stacked):
    shard_values, planes, exists, sign = stacked
    counts = bsi.range_count_batch(
        planes, exists, sign, _encode(_QUERIES), depth=DEPTH
    )
    assert len(counts) == len(_QUERIES)
    for qi, (op, v) in enumerate(_QUERIES):
        want = sum(len(_np_match(values, op, v)) for values in shard_values)
        assert counts[qi] == want, (op, v)


def _filter_stacks(rng, exists, n_rows):
    """A ``[S, R, W]`` set-field stack of random rows and the per-shard
    column sets of each row."""
    words = rng.integers(
        0, 1 << 32, size=(S, n_rows, exists.shape[-1]), dtype=np.uint64
    ).astype(np.uint32)
    cols = [[_cols(words[si, r]) for r in range(n_rows)] for si in range(S)]
    return words, cols


@pytest.mark.parametrize(
    "name,queries",
    [
        # single-bound flights, the filled sign class among them: the
        # shards hold negative values, so "< positive" fills every
        # negative column and may count only the filtered ones
        ("single", [q for q in _QUERIES if isinstance(q[1], int)]),
        ("filled", [("<", 37), ("<=", 1023), (">", -37), (">=", -1023)]),
        ("notnull", [("!=", None)]),
        # two-bound flights (and a mixed one: the second bound of a
        # single-bound query is the neutral "any")
        ("two", [q for q in _QUERIES if isinstance(q[1], tuple)]),
        ("mixed", _QUERIES),
        ("pad", _QUERIES[:3]),  # 3 pads to 4
    ],
)
def test_range_count_filtered_batch(stacked, name, queries):
    """The filtered count kernel against numpy: every query's range
    predicate intersected with one or two gathered set rows, an absent
    row (slot -1) counting nothing, padding rows ignored."""
    shard_values, planes, exists, sign = stacked
    rng = np.random.default_rng(17)
    bits = np.concatenate(
        [exists[:, None], sign[:, None], planes], axis=1
    )  # the raw stack layout: exists, sign, planes
    a, a_cols = _filter_stacks(rng, exists, 5)
    b, b_cols = _filter_stacks(rng, exists, 3)
    slots = np.array(
        [[qi % 5, (qi % 4) - 1] for qi in range(len(queries))], np.int32
    )
    counts = bsi.range_count_filtered_batch(
        bits, _encode(queries), [a, b], slots, depth=DEPTH
    )
    assert len(counts) == len(queries)
    one = bsi.range_count_filtered_batch(
        bits, _encode(queries), [a], slots[:, :1], depth=DEPTH
    )
    for qi, (op, v) in enumerate(queries):
        sa, sb = slots[qi]
        want2 = want1 = 0
        for si, values in enumerate(shard_values):
            m = _np_match(values, op, v) & a_cols[si][sa]
            want1 += len(m)
            if sb >= 0:
                want2 += len(m & b_cols[si][sb])
        assert counts[qi] == want2, (op, v, "two leaves")
        assert one[qi] == want1, (op, v, "one leaf")


def test_depth_edge_one_bit(stacked):
    """depth=1 exercises the scan with a single plane."""
    rng = np.random.default_rng(3)
    values, frag = _make_shard(rng, depth=1)
    p, e, sg = frag.bsi_tensors(1)
    queries = [("<", 0), ("<=", 0), (">", -1), ("==", 1), ("==", -1), ("!=", 0)]
    masks = np.asarray(
        bsi.range_batch(
            p[None], e[None], sg[None], _encode(queries), depth=1
        )
    )
    for qi, (op, v) in enumerate(queries):
        assert _cols(masks[qi, 0]) == _np_match(values, op, v), (op, v)


def test_pow2_padding_is_inert(stacked):
    """A flight of 3 pads to 4; the padded slot must not disturb the
    useful ones (same bits as an unpadded batch of the same queries)."""
    _, planes, exists, sign = stacked
    queries = [("<", 10), (">", -10), ("==", 0)]
    m3 = np.asarray(
        bsi.range_batch(planes, exists, sign, _encode(queries), depth=DEPTH)
    )
    assert m3.shape[0] == 4
    m4 = np.asarray(
        bsi.range_batch(
            planes, exists, sign, _encode(queries + [("!=", None)]),
            depth=DEPTH,
        )
    )
    assert np.array_equal(m3[:3], m4[:3])


def test_condition_bounds_rejects_unknown():
    with pytest.raises(ValueError):
        bsi.condition_bounds("~", 3)
    with pytest.raises(ValueError):
        bsi.condition_bounds("==", None)


def test_encode_rejects_bad_shapes():
    with pytest.raises(ValueError):
        bsi.encode_query_bounds([[]], DEPTH)
    with pytest.raises(ValueError):
        bsi.encode_query_bounds(
            [[("<", 1)], [("<", 2)]], DEPTH, q_pad=1
        )


def test_sum_batch_matches_per_query(stacked):
    shard_values, planes, exists, sign = stacked
    rng = np.random.default_rng(5)
    W = exists.shape[-1]
    # filter 0: everything; 1: random halves; 2: empty
    filters = np.stack(
        [
            exists,
            rng.integers(0, 1 << 32, size=(S, W), dtype=np.uint64).astype(
                np.uint32
            ),
            np.zeros((S, W), np.uint32),
        ],
        axis=1,
    )
    acc = bsi._sum_batch_kernel(planes, exists, sign, filters)
    got = bsi.sum_pairs(acc, depth=DEPTH, n=3)
    assert len(got) == 3
    for q in range(3):
        total, count = 0, 0
        for si in range(S):
            t, c = bsi.sum_host(
                planes[si], exists[si], sign[si], filters[si, q], depth=DEPTH
            )
            total += t
            count += c
        assert got[q] == (total, count), q
    # ground truth for the unfiltered slot
    want_total = sum(sum(v.values()) for v in shard_values)
    want_count = sum(len(v) for v in shard_values)
    assert got[0] == (want_total, want_count)
    assert got[2] == (0, 0)


def test_sum_batch_supported_gate():
    assert bsi.sum_batch_supported(16, 2048)
    assert not bsi.sum_batch_supported(1 << 20, 1 << 12)


def test_batched_dispatch_telemetry_labels(stacked):
    """The (depth, Q-bucket) compile keys and the padded-vs-useful
    query split must be observable: ?profile=true kernel records carry
    depth/qBucket/qUseful, and pilosa_kernel_* counters gain the
    depth:/qbucket: tags plus padded/useful query counts."""
    from pilosa_tpu.obs import qprofile
    from pilosa_tpu.ops import kernels

    _, planes, exists, sign = stacked
    queries = _encode([("<", 10), (">", -10), ("==", 0)])  # pads 3 -> 4
    prof = qprofile.QueryProfile("i", "batch")
    with qprofile.activate(prof):
        bsi.range_batch(planes, exists, sign, queries, depth=DEPTH)
    recs = [
        r
        for n in [prof.root] + prof.root.children
        for r in n.kernels
        if r.get("kernel") == "bsi_range_batch"
    ]
    assert recs, prof.to_dict()
    rec = recs[-1]
    assert rec["depth"] == DEPTH
    assert rec["qBucket"] == 4 and rec["qUseful"] == 3
    snap = kernels.kernel_stats.snapshot()["counters"]
    dispatch = [
        k
        for k in snap
        if k.startswith("kernel_dispatch")
        and "kernel:bsi_range_batch" in k
        and f"depth:{DEPTH}" in k
        and "qbucket:4" in k
    ]
    assert dispatch, sorted(snap)
    padded = [
        k
        for k in snap
        if k.startswith("kernel_padded_queries")
        and "kernel:bsi_range_batch" in k
    ]
    assert padded and snap[padded[0]] >= 1
