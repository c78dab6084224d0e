"""Bit-sliced-index (BSI) kernels.

The reference stores integer fields bit-sliced: row 0 = exists bit, row 1 =
sign bit, rows 2..2+bitDepth = magnitude bit-planes (reference
fragment.go:90-96 ``bsiExistsBit/bsiSignBit/bsiOffsetBit``), and runs range
queries as sequential bit-plane scans (reference fragment.go:1271-1534) and
Sum as popcount-per-plane place-value math (reference fragment.go:1130-1138).

Here each kernel takes the magnitude planes as a dense ``uint32[depth, W]``
tensor (LSB plane first) plus ``exists``/``sign``/``filter`` word vectors and
evaluates the whole scan as an unrolled jitted loop over planes — ``depth``
is a static Python int (<= 64), so each (op, depth) pair compiles once and
the plane loop fuses into a handful of vector ops on the VPU.

The kernels are shape-polymorphic over a leading shard axis: pass
``planes[S, depth, W]`` with ``exists/sign/filter[S, W]`` and the same
compiled scan serves a whole stacked field in ONE launch (the executor's
BSI serving stacks), with word-axis reductions kept per shard for
int32-exactness and Min/Max candidate reductions global across shards.

Values are stored as offset-from-base two's-complement-free sign/magnitude:
stored = value - base; sign row holds stored < 0; planes hold abs(stored).
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _bound_args(value_abs: int, depth: int):
    """Encode a query bound's magnitude as traced kernel inputs: its low
    ``depth`` bits as a uint32 vector plus an out-of-band flag for
    ``value_abs >= 2^depth``. Keeping the bound traced (not static) means
    each (op, depth, sign-variant) compiles exactly once no matter how many
    distinct bounds a workload queries."""
    bits = jnp.asarray([(value_abs >> k) & 1 for k in range(depth)], jnp.uint32)
    oob = jnp.asarray(value_abs >= (1 << depth))
    return bits, oob


def _select(plane, bit):
    """plane if bit else ~plane, with a traced bit."""
    return jnp.where(bit == 1, plane, ~plane)


@partial(jax.jit, static_argnames=("negative", "depth"))
def _range_eq_kernel(planes, exists, sign, bits, oob, *, negative: bool, depth: int):
    b = exists & (sign if negative else ~sign)
    for k in range(depth):
        b = b & _select(planes[..., k, :], bits[k])
    # A bound outside the representable magnitude can equal nothing.
    return jnp.where(oob, jnp.zeros_like(b), b)


def range_eq(planes, exists, sign, *, value_abs: int, negative: bool, depth: int):
    """Columns whose stored value == ±value_abs (reference fragment.go:1286)."""
    bits, oob = _bound_args(value_abs, depth)
    return _range_eq_kernel(
        planes, exists, sign, bits, oob, negative=negative, depth=depth
    )


def _mag_lt(planes, candidates, bits, oob, depth: int, allow_eq: bool):
    """Among candidates, magnitude < bound (or <= when allow_eq). A bound
    >= 2^depth exceeds every stored magnitude, so all candidates match."""
    lt = jnp.zeros_like(candidates)
    eq = candidates
    for k in reversed(range(depth)):
        p = planes[..., k, :]
        lt = lt | jnp.where(bits[k] == 1, eq & ~p, jnp.zeros_like(eq))
        eq = eq & _select(p, bits[k])
    out = (lt | eq) if allow_eq else lt
    return jnp.where(oob, candidates, out)


def _mag_gt(planes, candidates, bits, oob, depth: int, allow_eq: bool):
    """Among candidates, magnitude > bound (or >= when allow_eq). A bound
    >= 2^depth exceeds every stored magnitude, so nothing matches."""
    gt = jnp.zeros_like(candidates)
    eq = candidates
    for k in reversed(range(depth)):
        p = planes[..., k, :]
        gt = gt | jnp.where(bits[k] == 1, jnp.zeros_like(eq), eq & p)
        eq = eq & _select(p, bits[k])
    out = (gt | eq) if allow_eq else gt
    return jnp.where(oob, jnp.zeros_like(out), out)


@partial(jax.jit, static_argnames=("negative", "depth", "allow_eq"))
def _range_lt_kernel(planes, exists, sign, bits, oob, *, negative, depth, allow_eq):
    neg = exists & sign
    nonneg = exists & ~sign
    if not negative:
        return neg | _mag_lt(planes, nonneg, bits, oob, depth, allow_eq)
    return _mag_gt(planes, neg, bits, oob, depth, allow_eq)


def range_lt(planes, exists, sign, *, value: int, depth: int, allow_eq: bool):
    """Columns with stored value < value (<= when allow_eq).

    Mirrors the sign-split logic of the reference's rangeLT
    (fragment.go:1378-1445): for a non-negative bound all negatives match
    plus non-negatives with small-enough magnitude; for a negative bound
    only negatives with large-enough magnitude match.
    """
    bits, oob = _bound_args(abs(value), depth)
    return _range_lt_kernel(
        planes, exists, sign, bits, oob,
        negative=value < 0, depth=depth, allow_eq=allow_eq,
    )


@partial(jax.jit, static_argnames=("negative", "depth", "allow_eq"))
def _range_gt_kernel(planes, exists, sign, bits, oob, *, negative, depth, allow_eq):
    neg = exists & sign
    nonneg = exists & ~sign
    if not negative:
        return _mag_gt(planes, nonneg, bits, oob, depth, allow_eq)
    return nonneg | _mag_lt(planes, neg, bits, oob, depth, allow_eq)


def range_gt(planes, exists, sign, *, value: int, depth: int, allow_eq: bool):
    """Columns with stored value > value (>= when allow_eq); reference
    fragment.go:1447-1514."""
    bits, oob = _bound_args(abs(value), depth)
    return _range_gt_kernel(
        planes, exists, sign, bits, oob,
        negative=value < 0, depth=depth, allow_eq=allow_eq,
    )


def range_between(planes, exists, sign, *, lo: int, hi: int, depth: int):
    """lo <= stored <= hi (reference fragment.go:1516-1534 rangeBetween)."""
    a = range_gt(planes, exists, sign, value=lo, depth=depth, allow_eq=True)
    b = range_lt(planes, exists, sign, value=hi, depth=depth, allow_eq=True)
    return a & b


@partial(jax.jit, static_argnames=("depth",))
def sum_count(planes, exists, sign, filter_words, *, depth: int):
    """(sum of stored values, count) over filtered columns.

    Place-value popcount per plane, positives minus negatives (reference
    fragment.go:1109-1160). Returns float64-safe int64 math on host side by
    keeping per-plane int32 popcounts; totals are combined in int64 here
    (CPU) / via two int32 halves (TPU handles int64 emulation for scalars).
    """
    f = exists & filter_words
    pos = f & ~sign
    neg = f & sign
    pos_counts = []
    neg_counts = []
    for k in range(depth):
        p = planes[..., k, :]
        # per-(leading-dim) word-axis sums stay int32-exact (<= W*32 per
        # shard); the host combines them in arbitrary precision
        pos_counts.append(
            jnp.sum(lax.population_count(p & pos).astype(jnp.int32), axis=-1)
        )
        neg_counts.append(
            jnp.sum(lax.population_count(p & neg).astype(jnp.int32), axis=-1)
        )
    count = jnp.sum(lax.population_count(f).astype(jnp.int32), axis=-1)
    return (
        jnp.stack(pos_counts) if depth else jnp.zeros((0,), jnp.int32),
        jnp.stack(neg_counts) if depth else jnp.zeros((0,), jnp.int32),
        count,
    )


def sum_host(planes, exists, sign, filter_words, *, depth: int) -> tuple[int, int]:
    """Host wrapper: exact arbitrary-precision (sum, count) from the
    per-plane device popcounts."""

    from pilosa_tpu.ops import kernels

    with kernels.enqueue("bsi_sum"):
        pos_c, neg_c, count = sum_count(
            planes, exists, sign, filter_words, depth=depth
        )
    # ONE pull per tensor (a per-plane loop of np.asarray would pay a
    # host round trip per plane)
    pos_np = kernels.pull(pos_c, "bsi_sum").astype(np.int64)
    neg_np = kernels.pull(neg_c, "bsi_sum").astype(np.int64)
    pos_sums = pos_np.reshape(depth, -1).sum(axis=1) if depth else []
    neg_sums = neg_np.reshape(depth, -1).sum(axis=1) if depth else []
    total = sum(int(c) << k for k, c in enumerate(pos_sums)) - sum(
        int(c) << k for k, c in enumerate(neg_sums)
    )
    return total, int(kernels.pull(count, "bsi_sum").astype(np.int64).sum())


@partial(jax.jit, static_argnames=("depth", "maximal"))
def extreme_mag(planes, candidates, *, depth: int, maximal: bool):
    """(magnitude, surviving-candidate words) of the max (or min) magnitude
    among candidate columns. Empty candidate set returns (0, zeros)."""
    c = candidates
    mag = jnp.zeros((), jnp.int32)
    nonempty = jnp.any(candidates != 0)
    for k in reversed(range(depth)):
        p = planes[..., k, :]
        hit = c & (p if maximal else ~p)
        any_hit = jnp.any(hit != 0)
        c = jnp.where(any_hit, hit, c)
        bit_on = any_hit if maximal else ~any_hit
        mag = mag + jnp.where(bit_on, 1 << k if (1 << k) < 2**31 else 0, 0).astype(mag.dtype)
    return jnp.where(nonempty, mag, 0), c


@partial(jax.jit, static_argnames=("depth", "maximal"))
def _min_max_fused(planes, exists, sign, fw, *, depth: int, maximal: bool):
    """Both sign branches of Min/Max in ONE program: flags, magnitudes,
    counts, and survivor masks.  The host picks the branch from one
    scalar pull instead of issuing a sync per decision (each host sync
    is a full dispatch round trip)."""
    f = exists & fw
    neg = f & sign
    nonneg = f & ~sign
    # Branch a = preferred: Max prefers non-negatives (largest
    # magnitude), Min prefers negatives; the fallback branch takes the
    # opposite extreme of the magnitude.
    a, b = (nonneg, neg) if maximal else (neg, nonneg)
    mag_a, c_a = extreme_mag(planes, a, depth=depth, maximal=True)
    mag_b, c_b = extreme_mag(planes, b, depth=depth, maximal=False)
    cnt = lambda c: jnp.sum(lax.population_count(c).astype(jnp.int32))
    scalars = jnp.stack(
        [
            jnp.any(a != 0).astype(jnp.int32),
            jnp.any(b != 0).astype(jnp.int32),
            mag_a.astype(jnp.int32),
            cnt(c_a),
            mag_b.astype(jnp.int32),
            cnt(c_b),
        ]
    )
    return scalars, c_a, c_b


def min_max_host(planes, exists, sign, filter_words, *, depth: int, maximal: bool):
    """Host wrapper for Min/Max (reference fragment.go:1152-1225 minUnsigned/
    maxUnsigned + sign handling): returns (stored_value, count) or
    (0, 0) when no column matches.  One launch, one host pull (the
    survivor masks are pulled only for the depth >= 31 exact-magnitude
    recompute)."""
    from pilosa_tpu.ops import kernels

    with kernels.enqueue("bsi_min_max"):
        scalars, c_a, c_b = _min_max_fused(
            jnp.asarray(planes),
            jnp.asarray(exists),
            jnp.asarray(sign),
            jnp.asarray(filter_words),
            depth=depth,
            maximal=maximal,
        )
    has_a, has_b, mag_a, cnt_a, mag_b, cnt_b = (
        # ONE host pull for every decision
        kernels.pull(scalars, "bsi_min_max").tolist()
    )
    if not has_a and not has_b:
        return 0, 0
    # branch a's sign is + for Max (non-negatives), - for Min (negatives)
    a_positive = maximal
    if has_a:
        value = _exact_mag(planes, c_a, depth, int(mag_a))
        value = value if a_positive else -value
        return value, int(cnt_a)
    value = _exact_mag(planes, c_b, depth, int(mag_b))
    value = -value if a_positive else value
    return value, int(cnt_b)


# ---------------------------------------------------------------------------
# Query-batched kernels: Q range predicates per launch.
#
# The single-query kernels above compile one program per (op, depth,
# sign-variant) and pay a full dispatch per predicate, which drowns the
# engine under concurrent predicates.  The batched forms lift the traced
# bound to stacked per-query
# tensors so ONE launch evaluates a whole flight against shared
# ``planes[S, depth, W]``:
#
# * every condition op shares ONE compiled program per (depth, Q-bucket,
#   bound count, need): a query is 1-2 bounds, each encoded as per-plane
#   magnitude-bit word masks plus a meta row of composition masks.  The
#   comparison itself is two LSB-first borrow accumulators per bound —
#   ``A`` (magnitude </<= bound) and ``B`` (magnitude >/>= bound), with
#   strictness folded into the TRACED init word — and the value-space
#   result (sign split, not-null fill, ==/!= via A&B) is selected by
#   traced meta masks.  "<", "><", "!=", "==" are the same program with
#   different traced inputs;
# * a static ``need = (lo, hi)`` pair (a compile key) drops whichever
#   accumulator no bound in the flight reads: a uniform "<=" flight runs
#   one 4-op recurrence per plane instead of the full pair;
# * Q pads to a power of two (padding queries select nothing), so
#   drifting flight sizes reuse the same XLA program.
# ---------------------------------------------------------------------------

_ONES32 = np.uint32(0xFFFFFFFF)
_KSHIFT = np.arange(64)  # plane-index shifts for magnitude-bit expansion
_ZERO_META = [0] * 11    # shared all-zero meta row for padding slots

# comparison ops consumable by encode_query_bounds; "any" is the
# identity bound (matches every existing column).
_CMP_OPS = ("<", "<=", ">", ">=", "==", "!=", "any")

# qmeta channel indices (full-word masks unless noted)
_M_A0 = 0      # lo accumulator init: 0 = strict (<), ONES = non-strict (<=)
_M_B0 = 1      # hi accumulator init: 0 = strict (>), ONES = non-strict (>=)
_M_OOB = 2     # |bound| >= 2^depth: forces A=ONES, B=0
_M_FNEG = 3    # unconditionally include negative columns
_M_FNON = 4    # unconditionally include non-negative columns
_M_SNEG = 5    # apply the compare term to negative columns
_M_SNON = 6    # apply the compare term to non-negative columns
_M_XOR = 7     # invert the compare term (!=)
_M_SELA = 8    # term reads A
_M_SELB = 9    # term reads B
_M_SELC = 10   # term reads A & B (==/!= equality)
_M_CH = 11


def condition_bounds(op: str, value) -> list[tuple[str, int]]:
    """A PQL condition op as 1-2 ``(cmp, stored_bound)`` bounds consumable
    by :func:`encode_query_bounds` (cmp one of ``_CMP_OPS``).  ``value``
    is already base-adjusted (stored space).  ``!= None`` (not-null) is
    the unconditional bound.  Raises ValueError for unsupported shapes."""
    if op == "!=" and value is None:
        return [("any", 0)]
    if op in ("<", "<=", ">", ">=", "==", "!="):
        if value is None:
            raise ValueError(f"condition {op} requires a value")
        return [(op, int(value))]
    if op == "><":
        lo, hi = value
        return [(">=", int(lo)), ("<=", int(hi))]
    if op in ("<x<", "<=x<", "<x<=", "<=x<="):
        lo, hi = value
        lo_op, hi_op = op.split("x")
        return [
            (">=" if lo_op == "<=" else ">", int(lo)),
            ("<=" if hi_op == "<=" else "<", int(hi)),
        ]
    raise ValueError(f"unsupported condition op: {op}")


def encode_query_bounds(queries, depth: int, q_pad: int | None = None):
    """Pack per-query bound lists into the batched kernels' traced inputs:
    ``(qmask[P,B,depth], qinv[P,B,depth], qmeta[P,B,11])`` uint32
    full-word masks (0 / 0xFFFFFFFF), padded to ``q_pad`` queries (padding
    rows select nothing).  ``qmask`` holds the bound magnitude bits as
    per-plane words, ``qinv`` their complement (so the kernels' equality
    term is a single xor), and ``qmeta`` the ``_M_*`` composition
    channels.  Each query is a list of 1-2 ``(cmp, stored_bound)``
    tuples; ``B`` is the flight's max bound count, so an all-single-bound
    flight compiles the cheaper one-scan program.

    Also returns ``need = (lo, hi)``: which borrow accumulators any bound
    in the flight actually reads.  The pair is a compile key — a uniform
    "<="/"<" flight never builds the hi-side recurrence.  Out-of-band
    bounds (``|bound| >= 2^depth``) and the "any" identity read neither:
    their result is decided by the meta masks alone."""
    Q = len(queries)
    P = Q if q_pad is None else q_pad
    if P < Q:
        raise ValueError("q_pad smaller than the query count")
    for bounds in queries:
        if not 1 <= len(bounds) <= 2:
            raise ValueError("each query takes 1-2 bounds")
    B = max((len(b) for b in queries), default=1)
    # stage per-bound scalars in plain python (list sets are ~10x
    # cheaper than numpy scalar assignment at flight sizes), then expand
    # to full-word masks in one vectorized stroke per flight
    mags = [0] * (P * B)
    meta_rows = [_ZERO_META] * (P * B)
    need_lo = need_hi = False
    lim = 1 << depth
    for qi, bounds in enumerate(queries):
        for j in range(B):
            # a missing second bound is the neutral "any" (r & exists)
            cmp_, bound = bounds[j] if j < len(bounds) else ("any", 0)
            meta = [0] * _M_CH
            meta_rows[qi * B + j] = meta
            if cmp_ == "any":
                meta[_M_FNEG] = meta[_M_FNON] = 1
                continue
            if cmp_ not in _CMP_OPS:
                raise ValueError(f"unsupported comparison: {cmp_}")
            mag = abs(int(bound))
            neg = bound < 0
            oob = mag >= lim
            if oob:
                meta[_M_OOB] = 1
            else:
                mags[qi * B + j] = mag
            meta[_M_SNEG if neg else _M_SNON] = 1
            if cmp_ in ("==", "!="):
                meta[_M_A0] = meta[_M_B0] = 1
                meta[_M_SELC] = 1
                if cmp_ == "!=":
                    meta[_M_XOR] = 1
                    meta[_M_FNON if neg else _M_FNEG] = 1
                lo = hi = not oob
            else:
                # value-space </<= of a non-negative bound (or >/>= of a
                # negative one) is the LO side of the magnitude compare;
                # the mirrored cases are the HI side.  The opposite sign
                # class matches unconditionally for </<= nonneg and >/>=
                # neg (fill), and never otherwise.
                lo = (cmp_[0] == "<") != neg
                hi = not lo
                if cmp_.endswith("="):
                    meta[_M_A0 if lo else _M_B0] = 1
                meta[_M_SELA if lo else _M_SELB] = 1
                if cmp_[0] == ("<" if not neg else ">"):
                    meta[_M_FNON if neg else _M_FNEG] = 1
                lo, hi = lo and not oob, hi and not oob
            need_lo = need_lo or lo
            need_hi = need_hi or hi
    # bit k of |bound| -> plane-k word all-ones
    mag_arr = np.asarray(mags, np.int64).reshape(P, B, 1)
    qmask = ((mag_arr >> _KSHIFT[:depth]) & 1).astype(np.uint32) * _ONES32
    qmeta = np.asarray(meta_rows, np.uint32).reshape(P, B, _M_CH) * _ONES32
    qinv = ~qmask
    # padding rows keep qinv = ONES: the accumulators they drag along
    # stay all-zero and the zero meta row selects nothing
    return qmask, qinv, qmeta, (need_lo, need_hi)


def _bound_term(planes, bm, binv, meta, depth: int, need):
    """Compare term for one encoded bound, sign split not yet applied.
    Two LSB-first borrow accumulators walk the planes — ``A`` =
    magnitude </<= bound, ``B`` = magnitude >/>= bound, strictness
    chosen by the traced init words — then the select masks compose
    the ==/!= equality via ``A & B`` and the ``!=`` inversion."""
    shape = planes.shape[:-2] + planes.shape[-1:]
    A = jnp.broadcast_to(meta[_M_A0], shape)
    Bm = jnp.broadcast_to(meta[_M_B0], shape)
    for k in range(depth):  # LSB -> MSB: the last plane dominates
        p = planes[..., k, :]
        x = p ^ bm[k]  # plane bit != bound bit
        # bm & ~p == bm & x and p & ~bm == x & binv, so each side is one
        # xor + and + andnot + or per plane
        if need[0]:
            A = (bm[k] & x) | (A & ~x)
        if need[1]:
            Bm = (x & binv[k]) | (Bm & ~x)
    A = A | meta[_M_OOB]       # oob bound exceeds every magnitude
    Bm = Bm & ~meta[_M_OOB]
    return meta[_M_XOR] ^ (
        (meta[_M_SELA] & A)
        | (meta[_M_SELB] & Bm)
        | (meta[_M_SELC] & A & Bm)
    )


def _bound_eval(planes, neg_cols, nonneg_cols, bm, binv, meta, depth: int, need):
    """Columns matching one encoded bound: the compare term applied to
    its selected sign classes, plus the fill of the opposite class.
    The encoder never fills and selects the SAME sign class, so the two
    halves of the OR are disjoint — count-only callers exploit that."""
    term = _bound_term(planes, bm, binv, meta, depth, need)
    return (
        (meta[_M_FNEG] & neg_cols)
        | (meta[_M_FNON] & nonneg_cols)
        | (((meta[_M_SNEG] & neg_cols) | (meta[_M_SNON] & nonneg_cols)) & term)
    )


def _query_eval(planes, neg_cols, nonneg_cols, mB, iB, tB, depth: int, need):
    r = _bound_eval(planes, neg_cols, nonneg_cols, mB[0], iB[0], tB[0], depth, need)
    for bi in range(1, mB.shape[0]):
        r = r & _bound_eval(
            planes, neg_cols, nonneg_cols, mB[bi], iB[bi], tB[bi], depth, need
        )
    return r


def bounds_width(depth: int) -> int:
    """Words a query's two bounds take in :func:`pack_bounds`' row."""
    return 2 * (depth + _M_CH)


def pack_bounds(queries, depth: int, q_pad: int) -> np.ndarray:
    """One row a query for the encoded bounds of ``queries`` where a
    program takes a range predicate as a LEAF of a compiled tree
    (exec/astbatch.py): ``uint32[q_pad, bounds_width(depth)]``, each of
    the two bounds its magnitude masks and then its meta row.  Always two
    bounds a query (a missing one is the neutral ``any``) and read with
    ``need = (True, True)``, so no drawn value, a band whose low end is 0
    or a bound out of range among them, is a compile key."""
    two = [list(b) + [("any", 0)] * (2 - len(b)) for b in queries]
    qmask, _, qmeta, _ = encode_query_bounds(two, depth, q_pad=q_pad)
    return np.concatenate([qmask, qmeta], axis=-1).reshape(q_pad, -1)


def range_words(bits, packed, *, depth: int):
    """Traced: the ``[S, W]`` words of one query's range predicate
    (``packed``: its row of :func:`pack_bounds`) over the raw BSI stack
    ``bits[S, depth+2, W]``, sliced in here."""
    exists, sign, planes = bits[:, 0], bits[:, 1], bits[:, 2:]
    packed = packed.reshape(2, depth + _M_CH)
    mB, tB = packed[:, :depth], packed[:, depth:]
    return _query_eval(
        planes, exists & sign, exists & ~sign, mB, ~mB, tB, depth,
        (True, True),
    )


@partial(jax.jit, static_argnames=("depth", "need"))
def _range_batch_kernel(planes, exists, sign, qmask, qinv, qmeta, *, depth: int, need):
    """[Q, ..., W] result masks for Q encoded range predicates in ONE
    launch.  Compile key: (depth, Q-bucket, bound count, need, stack
    shape)."""
    neg_cols = exists & sign
    nonneg_cols = exists & ~sign

    def one(mB, iB, tB):
        return _query_eval(planes, neg_cols, nonneg_cols, mB, iB, tB, depth, need)

    return jax.vmap(one)(qmask, qinv, qmeta)


def _count_one(planes, exists, sign, depth: int, need, n_bounds: int):
    """Per-query count closure shared by the batched count kernels.
    Single-bound flights skip materialising the fill half of the result
    mask: fill and the selected compare classes are disjoint sign
    classes by encoder construction, so the filled class contributes its
    (shared, precomputed) column count as a scalar while only
    ``sel & term`` is popcounted."""
    neg_cols = exists & sign
    nonneg_cols = exists & ~sign
    if n_bounds == 1:
        c_neg = jnp.sum(
            lax.population_count(neg_cols).astype(jnp.int32), axis=-1
        )
        c_non = jnp.sum(
            lax.population_count(nonneg_cols).astype(jnp.int32), axis=-1
        )

        def one(mB, iB, tB):
            meta = tB[0]
            term = _bound_term(planes, mB[0], iB[0], meta, depth, need)
            sel = (meta[_M_SNEG] & neg_cols) | (meta[_M_SNON] & nonneg_cols)
            cnt = jnp.sum(
                lax.population_count(sel & term).astype(jnp.int32), axis=-1
            )
            cnt = cnt + jnp.where(meta[_M_FNEG] != 0, c_neg, 0)
            return cnt + jnp.where(meta[_M_FNON] != 0, c_non, 0)

        return one

    def one(mB, iB, tB):
        r = _query_eval(planes, neg_cols, nonneg_cols, mB, iB, tB, depth, need)
        return jnp.sum(lax.population_count(r).astype(jnp.int32), axis=-1)

    return one


@partial(jax.jit, static_argnames=("depth", "need"))
def _range_count_batch_kernel(planes, exists, sign, qmask, qinv, qmeta, *, depth: int, need):
    """Per-query per-shard match counts ``int32[Q, S]``: vmap over the
    query bucket with the word-axis popcount reduce fused into the same
    launch, so the plane scans of the whole flight compile into one
    elementwise program over the stack (word sums stay int32-exact per
    shard; the host combines in int64)."""
    one = _count_one(planes, exists, sign, depth, need, qmask.shape[1])
    return jax.vmap(one)(qmask, qinv, qmeta)


@partial(jax.jit, static_argnames=("depth", "need"))
def _range_count_scan_kernel(planes, exists, sign, qmask, qinv, qmeta, *, depth: int, need):
    """Scan-over-queries fallback for stacks where the vmap form's
    [Q, S, W] intermediate would not fit comfortably: the working set
    stays one mask wide at the cost of re-reading the planes per query."""
    one = _count_one(planes, exists, sign, depth, need, qmask.shape[1])

    def step(carry, q):
        return carry, one(*q)

    _, counts = lax.scan(step, 0, (qmask, qinv, qmeta))
    return counts


@partial(jax.jit, static_argnames=("depth", "need"))
def _range_count_filtered_kernel(bits, qmask, qinv, qmeta, stacks, slots, *, depth: int, need):
    """Per-query per-shard counts ``int32[Q, S]`` of a range predicate
    intersected with set rows.  ``bits`` is the raw ``[S, depth+2, W]``
    BSI stack (exists, sign, planes), sliced in here so the caller pays
    no dispatch for the views; ``stacks`` holds one ``[S, R, W]`` field
    stack per filter leaf and ``slots`` the ``int32[Q, leaves]`` rows to
    gather from them (-1: an absent row, zero words, as the compiled-AST
    lane's leaves).  The filter narrows the sign classes before the
    bounds are applied, so a filled class counts only its filtered
    columns.  A scan over the queries: the working set stays one mask
    wide."""
    exists, sign, planes = bits[:, 0], bits[:, 1], bits[:, 2:]

    def step(carry, q):
        mB, iB, tB, sl = q
        f = exists
        for li, st in enumerate(stacks):
            s = sl[li]
            row = st[:, jnp.maximum(s, 0)]
            f = f & row & jnp.where(s >= 0, _ONES32, jnp.uint32(0))
        r = _query_eval(planes, f & sign, f & ~sign, mB, iB, tB, depth, need)
        return carry, jnp.sum(lax.population_count(r).astype(jnp.int32), axis=-1)

    _, counts = lax.scan(step, 0, (qmask, qinv, qmeta, slots))
    return counts


@lru_cache(maxsize=64)
def _range_count_filtered_mesh_fn(mesh, axis, n_stacks: int, depth: int, need):
    """jit(shard_map) of the filtered count over shards-sharded stacks:
    the scan is elementwise over the shard axis up to its count, so each
    device runs it on its own block of the BSI stack and of every filter
    stack, and the per-shard partials ``int32[Q, S]`` come back along
    the mesh axis for the host's int64 sum, as the one-device form's do.
    Bounds and slots are replicated."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(bits, qmask, qinv, qmeta, slots, *stacks):
        return _range_count_filtered_kernel(
            bits, qmask, qinv, qmeta, stacks, slots, depth=depth, need=need
        )

    stack_spec = P(axis, None, None)
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(stack_spec, P(None), P(None), P(None), P(None))
            + (stack_spec,) * n_stacks,
            out_specs=P(None, axis),
            check_vma=False,
        )
    )


# above this many bytes of [Q-bucket, S, W] flight masks, batched counts
# take the scan kernel (planes re-read per query, but no Q-wide state)
_COUNT_BATCH_VMAP_LIMIT = 256 << 20


def _batch_args(queries, depth: int):
    from pilosa_tpu.ops.bitops import pow2_pad_len

    from pilosa_tpu.ops import kernels

    P = pow2_pad_len(len(queries))
    qmask, qinv, qmeta, need = encode_query_bounds(queries, depth, q_pad=P)
    return (kernels.h2d(qmask), kernels.h2d(qinv), kernels.h2d(qmeta)), need


def _host_totals(counts, kernel: str, n: int) -> list[int]:
    """One pull of a count kernel's per-shard int32 partials ``[P, ...]``,
    summed per query in int64 on the host; the pow2-padding tail past
    ``n`` is dropped."""
    from pilosa_tpu.ops import kernels

    arr = kernels.pull(counts, kernel).astype(np.int64)
    return [int(c) for c in arr.reshape(arr.shape[0], -1).sum(axis=1)[:n]]


def range_batch(planes, exists, sign, queries, *, depth: int):
    """Batched Range: ``masks[P, ..., W]`` for the encoded ``queries``
    (list of bound lists, see :func:`condition_bounds`); the first
    ``len(queries)`` slices are the per-query results, the pow2-padding
    tail is garbage the caller must ignore."""
    from pilosa_tpu.ops import kernels

    args = _batch_args(queries, depth)
    with kernels.enqueue("bsi_range_batch") as sp:
        out = _range_batch_kernel(
            planes, exists, sign, *args[0], depth=depth, need=args[1]
        )
    kernels.note_bsi_dispatch(
        "bsi_range_batch",
        wall=sp.duration,
        args=(planes, args[0][0]),
        depth=depth,
        q_bucket=int(args[0][0].shape[0]),
        q_useful=len(queries),
    )
    return out


def range_count_batch(planes, exists, sign, queries, *, depth: int):
    """Batched Count(Range): per-query int64 match counts (host-side
    exact sum of the per-shard int32 partials)."""
    from pilosa_tpu.ops import kernels

    args, need = _batch_args(queries, depth)
    P = int(args[0].shape[0])
    mask_bytes = P * int(np.prod(exists.shape)) * 4
    kern = (
        _range_count_batch_kernel
        if mask_bytes <= _COUNT_BATCH_VMAP_LIMIT
        else _range_count_scan_kernel
    )
    with kernels.enqueue("bsi_range_count_batch") as sp:
        counts = kern(planes, exists, sign, *args, depth=depth, need=need)
    kernels.note_bsi_dispatch(
        "bsi_range_count_batch",
        wall=sp.duration,
        args=(planes, args[0]),
        depth=depth,
        q_bucket=P,
        q_useful=len(queries),
    )
    return _host_totals(counts, "bsi_range_count_batch", len(queries))


def range_count_filtered_batch(bits, queries, stacks, slots, *, depth: int):
    """Batched ``Count(Intersect(set rows, range predicate))``: per-query
    int64 counts from ONE launch over the raw BSI stack ``bits`` and the
    filter leaves' field ``stacks`` (one per column of ``slots``,
    ``int32[len(queries), leaves]``).  Only the encoded bounds and the
    slots leave the host; the filter rows are gathered on the device.
    Stacks sharded over a serving mesh (all of them, the executor's
    layout) run as one SPMD launch, each device on its own shards."""
    from pilosa_tpu.ops import kernels

    args, need = _batch_args(queries, depth)
    P = int(args[0].shape[0])
    padded = np.full((P, slots.shape[1]), -1, np.int32)
    padded[: len(queries)] = slots
    slots_dev = kernels.h2d(padded)
    m = kernels.shards_axis_of(bits)
    with kernels.enqueue("bsi_range_count_filtered") as sp:
        if m is not None:
            counts = _range_count_filtered_mesh_fn(
                *m, len(stacks), depth, need
            )(bits, *args, slots_dev, *stacks)
        else:
            counts = _range_count_filtered_kernel(
                bits, *args, tuple(stacks), slots_dev, depth=depth, need=need
            )
    kernels.note_bsi_dispatch(
        "bsi_range_count_filtered",
        wall=sp.duration,
        args=(bits, args[0], *stacks),
        depth=depth,
        q_bucket=P,
        q_useful=len(queries),
    )
    return _host_totals(counts, "bsi_range_count_filtered", len(queries))


# int32 ceiling for the fused Sum matmul accumulator: per-plane popcounts
# accumulate ACROSS shards on device (unlike sum_count's per-shard
# partials), so the total column count must fit int32.
_SUM_BATCH_ACC_LIMIT = 2**31 - 1


def sum_batch_supported(S: int, W: int) -> bool:
    """Whether the fused batched Sum may accumulate across ``S`` shards
    in int32 (a device's share of a mesh-sharded stack: each device
    accumulates its own) — the `row_counts_supported`-style decline
    gate; callers fall back to the per-query host lane."""
    return S * W * 32 <= _SUM_BATCH_ACC_LIMIT


@jax.jit
def _sum_batch_kernel(planes, exists, sign, filters):
    """Fused popcount-reduction Sum over Q filters: gram-style int8
    unpack + MXU matmul of [depth+1 rows] x [2Q filter rows] per shard,
    accumulated over the shard scan — one launch answers every (plane,
    filter, sign-class) popcount the place-value combine needs.
    ``filters`` is ``uint32[S, Q, W]``; returns ``int32[depth+1, 2Q]``
    (positive columns first, then negative; row depth = exists counts)."""
    from pilosa_tpu.ops.kernels import _unpack_int8

    f = filters & exists[:, None, :]
    fpos = f & ~sign[:, None, :]
    fneg = f & sign[:, None, :]
    filt2 = jnp.concatenate([fpos, fneg], axis=1)  # [S, 2Q, W]
    rows = jnp.concatenate([planes, exists[:, None, :]], axis=1)

    def body(acc, sf):
        r, ff = sf
        g = lax.dot_general(
            _unpack_int8(r), _unpack_int8(ff),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc + g, None

    acc0 = jnp.zeros((rows.shape[1], filt2.shape[1]), jnp.int32)
    acc, _ = lax.scan(body, acc0, (rows, filt2))
    return acc


def sum_filters_acc(bits, filters):
    """Traced: :func:`_sum_batch_kernel` over the RAW stack
    ``bits[S, depth+2, W]``, sliced in here, for filter words a program
    built itself (exec/astbatch.py ``compiled_sum``)."""
    return _sum_batch_kernel(bits[:, 2:], bits[:, 0], bits[:, 1], filters)


def sum_pairs(acc, *, depth: int, n: int):
    """ONE pull of a fused Sum's accumulator (``int32[depth+1, 2Q]``, or
    one such a device along a leading mesh axis) and the place-value
    combine: ``[(sum, count), ...]`` of its first ``n`` queries, in
    python ints so totals past 2^63 stay exact."""
    from pilosa_tpu.ops import kernels

    acc = kernels.pull(acc, "bsi_sum_filtered").astype(np.int64)
    if acc.ndim == 3:
        acc = acc.sum(axis=0)  # one accumulator a device
    Q = acc.shape[1] // 2
    out = []
    for q in range(n):
        pos, neg = acc[:, q], acc[:, Q + q]
        total = sum(int(pos[k]) << k for k in range(depth)) - sum(
            int(neg[k]) << k for k in range(depth)
        )
        out.append((total, int(pos[depth]) + int(neg[depth])))
    return out


def _exact_mag(planes, survivors, depth: int, approx: int) -> int:
    """extreme_mag tracks magnitude in int32; for depth >= 31 recompute the
    exact magnitude from one surviving column on the host."""
    if depth < 31:
        return approx

    surv = np.asarray(survivors)
    s = None
    if surv.ndim == 2:  # stacked [S, W]: locate one surviving shard first
        s_idx = np.flatnonzero(surv.any(axis=1))
        if len(s_idx) == 0:
            return 0
        s = int(s_idx[0])
        surv = surv[s]
    idx = np.flatnonzero(np.unpackbits(surv.view(np.uint8), bitorder="little"))
    if len(idx) == 0:
        return 0
    col = int(idx[0])
    w, b = col >> 5, col & 31
    # slice the one surviving column's plane words device-side — pulling
    # the whole planes tensor would transfer the full field per query
    pl_col = np.asarray(planes[s, :, w] if s is not None else planes[:, w])
    mag = 0
    for k in range(depth):
        if (int(pl_col[k]) >> b) & 1:
            mag |= 1 << k
    return mag
