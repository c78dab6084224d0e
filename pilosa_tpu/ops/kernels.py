"""TPU kernels for the fragment hot loops.

The reference's performance-critical inner loops are the per-container
word loops in roaring/roaring.go:3078-4414 (AND/OR/XOR/ANDNOT + popcount,
e.g. ``intersectionCountBitmapBitmap`` roaring.go:568) and the TopN row
recount (fragment.go:459-498, 1568-1700).  On TPU those become:

* **The MXU gram path** (:func:`pair_gram`): ``popcount(a & b)`` is the
  dot product of the two rows viewed as 0/1 vectors, so a whole batch of
  ``Count(op(Row, Row))`` queries collapses into ONE scan of the index
  that unpacks each word block to int8 and accumulates a gram matrix
  ``G[i, j] = |row_i & row_j|`` on the systolic array.  Every pair op
  reduces to gram entries: ``|a|b| = G[aa]+G[bb]-G[ab]``,
  ``|a\\b| = G[aa]-G[ab]``, ``|a^b| = G[aa]+G[bb]-2G[ab]``.  The MXU
  turns 2*B row reads into one index read, and the fused-unpack Pallas
  variant keeps the 32x int8 expansion in VMEM instead of HBM; it is
  default ON on a TPU.
* **Fused XLA scans** for per-row popcounts (TopN) and everything else.
  Architecturally the cold scan is also mostly retired: unfiltered TopN
  serves from counts MAINTAINED across writes (core/fragment.py), so the
  scan only runs on stack rebuilds.  The Pallas row-scan variants that
  sat behind a ``PILOSA_TPU_PALLAS=1`` switch were REMOVED (PR 21): off
  by default, slower than the XLA scan in the old records, and refused
  by the chip's compiler at the widest row block their own tile budget
  admitted.  So were the earlier scalar-prefetch pair-count kernels:
  their one-row blocks violate the TPU (8, 128) tiling rule outright,
  and the gram path supersedes them.

Kernel and launch times on the current code: not measured; see
``PERF.md``.  ``tools/kernel_census.py`` compiles every ``pallas_call``
here for the chip and compares it with its XLA twin.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from functools import lru_cache, partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from pilosa_tpu.obs import devledger, qprofile, tracing
from pilosa_tpu.obs.stats import MemStatsClient
from pilosa_tpu.ops.bitops import pow2_pad_len

# Device cost ledger sites: every batched-kernel dispatch funnels through
# _note_dispatch, which claims the thread's XLA compile events and books
# the launch — BSI batched lanes report under their own site so the ledger
# splits standard-row vs BSI kernel costs.
_DL_KERNELS = devledger.site("ops.kernels")
_DL_BSI = devledger.site("ops.bsi")

logger = logging.getLogger(__name__)

_OPS = {
    "intersect": lambda a, b: a & b,
    "union": lambda a, b: a | b,
    "difference": lambda a, b: a & ~b,
    "xor": lambda a, b: a ^ b,
}


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _word_block(w: int, cap: int) -> int:
    """Largest power-of-two-ish divisor of ``w`` not exceeding ``cap``."""
    wb = min(w, cap)
    while w % wb:
        wb //= 2
    return max(wb, 1)


# ---------------------------------------------------------------------------
# Batched pair count: Count(op(Row(ra[i]), Row(rb[i]))) for i in [0, B)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("op",))
def pair_count_batched_xla(
    bits: jax.Array, ras: jax.Array, rbs: jax.Array, *, op: str = "intersect"
) -> jax.Array:
    """Fallback: device-side scan over the query batch (not vmap, which
    would materialize the [B, S, W] gather). Returns int32[B, S] per-shard
    partials like the Pallas kernel."""

    def body(_, q):
        ra, rb = q
        words = _OPS[op](bits[:, ra], bits[:, rb])
        return None, jnp.sum(
            lax.population_count(words).astype(jnp.int32), axis=-1
        )

    _, counts = lax.scan(body, None, (ras, rbs))
    return counts


# Count of Pallas→XLA demotions, probe-time and proven alike: a kernel the
# compiler refuses at first use, a device OOM or a miscompiled shape would
# otherwise become invisible performance degradation.  Surfaced via
# /debug/vars and diagnostics (pallas_fallbacks) so operators — and
# chip_smoke.py, for which any demotion is a failure — can see them.
# Dispatch runs on the HTTP request pool, so the counter is locked.
_pallas_fallbacks: int = 0
_PALLAS_FALLBACK_LOG_EVERY = 10
_fallback_lock = threading.Lock()

# Process-wide kernel/dispatch telemetry, rendered as ``pilosa_kernel_*``
# by /metrics and snapshotted into /debug/vars.  Lives
# here rather than on the holder because dispatch decisions are made in
# this module, below any holder plumbing.
kernel_stats = MemStatsClient()


def pallas_fallback_count() -> int:
    with _fallback_lock:
        return _pallas_fallbacks


def _note_pallas_fallback(exc: Exception, kernel: str, probing: bool) -> None:
    """Count one demotion.  A PROBE-time failure — the compiler refusing
    the kernel at its first use — is answered by the XLA twin like any
    other, but never silently: it is logged as an error with the
    compiler's message, because it means a default path of this build
    does not run on this chip."""
    global _pallas_fallbacks
    with _fallback_lock:
        _pallas_fallbacks += 1
        n = _pallas_fallbacks
    kernel_stats.count("kernel_pallas_fallbacks")
    if probing:
        logger.error(
            "pallas kernel %s refused at first use; answering with the"
            " XLA twin: %s: %s",
            kernel, type(exc).__name__, exc,
        )
    elif n % _PALLAS_FALLBACK_LOG_EVERY == 1:
        logger.warning(
            "pallas kernel %s demoted to XLA fallback (#%d): %r",
            kernel, n, exc,
        )


def _shape_sig(args) -> tuple:
    return tuple(
        tuple(a.shape) for a in args if getattr(a, "shape", None) is not None
    )


def _note_dispatch(
    kernel: str,
    lane: str,
    *,
    wall: float | None = None,
    args=(),
    demoted: bool = False,
    padded_bytes: int = 0,
    useful_bytes: int = 0,
    extra: dict | None = None,
    extra_tags: tuple = (),
    dl_site=None,
) -> None:
    """Record one kernel dispatch: tagged counters/timings into
    ``kernel_stats`` plus a per-kernel record into the active query
    profile.  ``wall`` is launch wall time — device work may still be in
    flight unless the caller synchronized.  Compiles are the ledger's to
    count (``devledger.totals.compiles``, from ``jax.monitoring``).
    ``extra`` merges lane-specific labels into the profile record and
    ``extra_tags`` onto the dispatch counter (bounded cardinality is the
    caller's responsibility)."""
    if lane != "host":
        # Ledger booking: the jit call already returned on this thread, so
        # any XLA compiles it triggered sit in the thread stash — claim
        # them under this site, and book the launch + identity.
        site = dl_site or _DL_KERNELS
        key = (kernel, lane, _shape_sig(args))
        site.track_key(key)
        site.claim(sig=f"{kernel}/{lane}:{key[2]}")
        site.record_launch(wall or 0.0)
        if any(_multi_device(a) for a in args):
            site.record_mesh_launch()
    tagged = kernel_stats.with_tags(
        f"kernel:{kernel}", f"lane:{lane}", *extra_tags
    )
    tagged.count("kernel_dispatch")
    if demoted:
        tagged.count("kernel_demotions")
    if padded_bytes:
        tagged.count("kernel_padded_bytes", int(padded_bytes))
        tagged.count("kernel_useful_bytes", int(useful_bytes))
    if wall is not None:
        tagged.timing("kernel_dispatch", wall)
    rec: dict = {"kernel": kernel, "lane": lane}
    if wall is not None:
        rec["wall_ms"] = round(wall * 1e3, 3)
    if demoted:
        rec["demoted"] = True
    if padded_bytes:
        rec["padded_bytes"] = int(padded_bytes)
        rec["useful_bytes"] = int(useful_bytes)
    if extra:
        rec.update(extra)
    qprofile.record_kernel(**rec)


def note_bsi_dispatch(
    kernel: str,
    *,
    wall: float,
    args,
    depth: int,
    q_bucket: int,
    q_useful: int,
    lane: str = "xla",
) -> None:
    """BSI batched-lane dispatch: same pipeline as :func:`_note_dispatch`
    but labelled with the lane's (depth, Q-bucket) compile key and the
    padded-vs-useful query split, so the shape-keyed program cache the
    batched kernels compile against is observable in ``?profile=true``
    records and ``pilosa_kernel_*`` metrics."""
    _note_dispatch(
        kernel,
        lane,
        wall=wall,
        args=args,
        extra={"depth": int(depth), "qBucket": int(q_bucket),
               "qUseful": int(q_useful)},
        extra_tags=(f"depth:{depth}", f"qbucket:{q_bucket}"),
        dl_site=_DL_BSI,
    )
    if q_bucket > q_useful:
        # pow2 Q padding: queries, scaled to the per-query input bytes
        tagged = kernel_stats.with_tags(f"kernel:{kernel}")
        tagged.count("kernel_padded_queries", int(q_bucket - q_useful))
        tagged.count("kernel_useful_queries", int(q_useful))
    else:
        kernel_stats.with_tags(f"kernel:{kernel}").count(
            "kernel_useful_queries", int(q_useful)
        )


def note_transfer(nbytes: int, direction: str, dl_site=None) -> None:
    """Count host<->device traffic (``direction``: "h2d" | "d2h").
    ``dl_site`` routes the ledger booking to the caller's registered site
    (executor stack builds, fragment syncs); defaults to ops.kernels."""
    if nbytes:
        kernel_stats.with_tags(f"direction:{direction}").count(
            "kernel_transfer_bytes", int(nbytes)
        )
        qprofile.incr(f"transfer_{direction}_bytes", int(nbytes))
        site = dl_site or devledger.active_window_site() or _DL_KERNELS
        site.record_transfer(int(nbytes), direction)


def note_pad(kernel: str, padded_bytes: int, useful_bytes: int) -> None:
    """Padding accounting for pow2 batch/gather padding (callers that
    know the padded and useful extents but dispatch elsewhere)."""
    tagged = kernel_stats.with_tags(f"kernel:{kernel}")
    tagged.count("kernel_padded_bytes", int(padded_bytes))
    tagged.count("kernel_useful_bytes", int(useful_bytes))


def enqueue(kernel: str) -> tracing.Span:
    """The span over one jitted call until it returns, ``with
    enqueue(k) as sp: out = fn(...)``: the host's side of a launch
    (argument handling, a compile or a cache fetch, the enqueue), while
    the device may still be running.  ``sp.duration`` is the ``wall``
    the dispatch notes book, so the two time one interval."""
    return tracing.start_span("kernels.enqueue").set_tag("kernel", kernel)


def pull(out, kernel: str = "") -> np.ndarray:
    """The one place the served path waits for a device result and
    copies it to the host: under a ``kernels.pull`` span, counting the
    d2h bytes.  A numpy array passes through untimed."""
    if isinstance(out, np.ndarray):
        return out
    with tracing.start_span("kernels.pull") as sp:
        arr = np.asarray(out)
        sp.set_tag("kernel", kernel).set_tag("bytes", arr.nbytes)
    note_transfer(arr.nbytes, "d2h")
    return arr


def wait(out, kernel: str = ""):
    """``block_until_ready`` under the same ``kernels.pull`` span: a wait
    for the device that copies nothing."""
    with tracing.start_span("kernels.pull").set_tag("kernel", kernel):
        return jax.block_until_ready(out)


def h2d(host, sharding=None, dtype=None):
    """Host arguments to the device under a ``kernels.h2d`` span
    (``device_put`` onto ``sharding`` where given).  What is already a
    device array passes through."""
    if isinstance(host, jax.Array):
        return host
    with tracing.start_span("kernels.h2d") as sp:
        arr = np.asarray(host, dtype)
        sp.set_tag("bytes", arr.nbytes)
        if sharding is not None:
            return jax.device_put(arr, sharding)
        return jnp.asarray(arr)


def record_host_op(kernel: str) -> None:
    """Executor host-path ops (python/numpy row materialization) report
    through the same telemetry under lane=host."""
    _note_dispatch(kernel, "host")


def telemetry_snapshot() -> dict:
    """JSON-safe kernel-telemetry rollup for /debug/vars and tests:
    dispatch-lane counts, transfer bytes, pallas gate states."""
    snap = kernel_stats.snapshot()
    lanes: dict[str, int] = {}
    transfers: dict[str, int] = {}
    for label, v in snap["counters"].items():
        name, _, tagstr = label.partition("{")
        tags = dict(
            t.split(":", 1) for t in tagstr.rstrip("}").split(",") if ":" in t
        )
        if name == "kernel_dispatch":
            lane = tags.get("lane", "?")
            lanes[lane] = lanes.get(lane, 0) + int(v)
        elif name == "kernel_transfer_bytes":
            d = tags.get("direction", "?")
            transfers[d] = transfers.get(d, 0) + int(v)
    return {
        "pallas_fallbacks": pallas_fallback_count(),
        "gram_gates": {
            "self": {
                "ok": _self_gram_gate.ok,
                "fails": _self_gram_gate.fails,
            },
            "cross": {
                "ok": _cross_gram_gate.ok,
                "fails": _cross_gram_gate.fails,
            },
        },
        "dispatch_lanes": lanes,
        "transfer_bytes": transfers,
        "counters": snap["counters"],
    }


def _multi_device(x) -> bool:
    """True when ``x`` is laid out across more than one device.

    pallas_call is not sharding-aware: feeding it a NamedSharding'd stack
    would either fail or make XLA replicate the full bitmap onto every
    device — exactly the materialization the mesh layout avoids.  Arrays
    sharded over a leading ``shards``-style mesh axis take the shard_map
    path below (per-device Pallas on TPU); anything else multi-device
    keeps the fused-XLA path, whose jnp ops partition over the mesh and
    reduce over ICI."""
    try:
        return len(x.sharding.device_set) > 1
    except AttributeError:
        return False


def shards_axis_of(x):
    """(mesh, axis_name) when ``x`` is NamedSharding'd with ONLY its
    leading dimension split over one mesh axis — the serving-stack layout
    (executor field stacks: P("shards", None, ...)).  None otherwise."""
    s = getattr(x, "sharding", None)
    if not isinstance(s, NamedSharding) or len(s.device_set) <= 1:
        return None
    spec = tuple(s.spec)
    if not spec or spec[0] is None:
        return None
    first = spec[0]
    if isinstance(first, (tuple, list)):
        if len(first) != 1:
            return None
        first = first[0]
    if not isinstance(first, str):
        return None
    if any(p is not None for p in spec[1:]):
        return None
    return s.mesh, first


@lru_cache(maxsize=64)
def _pair_count_sharded_fn(mesh, axis, op, two_tensor):
    """jit(shard_map) answering a pair-count batch over a shards-sharded
    stack: each device runs the single-device scan on its local shard
    block; per-shard partials concatenate back along the shard axis —
    the ICI replacement for the reference's per-node mapReduce fan-out
    (executor.go:2454-2611)."""
    if two_tensor:
        local = partial(pair_count_two_batched_xla, op=op)
        in_specs = (P(axis, None, None), P(axis, None, None), P(None), P(None))
    else:
        local = partial(pair_count_batched_xla, op=op)
        in_specs = (P(axis, None, None), P(None), P(None))
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=P(None, axis),
        )
    )


@lru_cache(maxsize=64)
def _row_counts_mesh_fn(mesh, axis, in_program_reduce):
    """jit(shard_map) row popcounts over a shards-sharded stack — per-
    shard int32[S, R] partials along the mesh axis for a host-side sum,
    or an in-program psum reduce to a replicated int32[R] for
    process-spanning meshes (same two modes as _gram_mesh_fn)."""
    if in_program_reduce:
        local = lambda b: lax.psum(row_counts_xla(b), axis)
        out_specs = P(None)
    else:
        local = row_counts_per_shard_xla
        out_specs = P(axis, None)
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis, None, None),),
            out_specs=out_specs,
        )
    )


def _timed_xla(kernel: str, fn, *args) -> jax.Array:
    """Launch an XLA-only kernel and book the dispatch."""
    with enqueue(kernel) as sp:
        out = fn(*args)
    _note_dispatch(kernel, "xla", wall=sp.duration, args=args)
    return out


def pair_count_batched(
    bits: jax.Array, ras: jax.Array, rbs: jax.Array, *, op: str = "intersect"
):
    """Pair counts for a query batch.  Local stacks return device
    ``int32[B, S]`` per-shard partials (callers sum host-side); on a
    PROCESS-SPANNING mesh those partials are not host addressable, so
    the reduce happens in-program — chunked psum with (hi, lo)
    carry-save past int32 — and the result is replicated
    ``np.int64[B]`` totals (already summed over shards)."""
    m = shards_axis_of(bits)
    if m is not None:
        mesh, axis = m
        if mesh_spans_processes(mesh):
            _, _, W = bits.shape
            chunk = _psum_chunk_size(mesh, W)
            if chunk < 1:
                raise ValueError(
                    "pair totals exceed int32 even per single psum"
                    " slice; shrink the shard width or the per-host mesh"
                )
            hi, lo = _psum_chunked_fn(mesh, axis, "pair:" + op, chunk)(
                bits, ras, rbs
            )
            out = _hi_lo_total(hi, lo)
            _note_dispatch("pair_count", "xla", args=(bits, ras))
            return out
        with enqueue("pair_count") as sp:
            out = _pair_count_sharded_fn(mesh, axis, op, False)(bits, ras, rbs)
        _note_dispatch("pair_count", "xla", wall=sp.duration, args=(bits, ras))
        return out
    with enqueue("pair_count") as sp:
        out = pair_count_batched_xla(bits, ras, rbs, op=op)
    _note_dispatch("pair_count", "xla", wall=sp.duration, args=(bits, ras))
    return out


# ---------------------------------------------------------------------------
# MXU gram path: all-pairs intersection counts as int8 matmuls
# ---------------------------------------------------------------------------

# Word-block the gram scan unpacks per step: [R, wb] uint32 -> [R, wb*32]
# int8 staged for the MXU.  4096 words = 2^17 bits/row/step; per-step gram
# partials (<= 2^17 per pair) accumulate exactly in int32.
_GRAM_WB = 4096

# Past this many distinct rows the gram matrix itself gets big (U^2 int32)
# and the O(U^2) matmul work outgrows the O(B) scan — callers fall back.
GRAM_MAX_ROWS = 4096

# numpy (not jnp): a device constant created during a jit trace would be a
# tracer and must not be cached across traces
_SHIFTS32 = np.arange(32, dtype=np.uint32)


def _gram_word_block(w: int) -> int:
    return _word_block(w, _GRAM_WB)


def _gram_blocks(bits: jax.Array, wb: int) -> jax.Array:
    """[S, R, W] -> [S*nb, R, wb] word blocks in scan order."""
    S, R, W = bits.shape
    nb = W // wb
    return bits.reshape(S, R, nb, wb).transpose(0, 2, 1, 3).reshape(
        S * nb, R, wb
    )


def _unpack_int8(blk: jax.Array) -> jax.Array:
    """[R, wb] uint32 words -> [R, wb*32] int8 0/1 for the MXU."""
    R, wb = blk.shape
    return ((blk[:, :, None] >> _SHIFTS32) & 1).astype(jnp.int8).reshape(
        R, wb * 32
    )


# fused-gram Pallas blocks: shards per step, and a VMEM budget for the
# in-kernel int8 unpack (R * wb * 32 bytes must fit comfortably)
_GRAM_PALLAS_SB = 8
_GRAM_PALLAS_UNPACK_BYTES = 4 << 20
# Scoped-VMEM ceiling handed to Mosaic for both gram kernels.  Its
# default on a v5e is 16 MiB of the core's 128 MiB, and the widest stack
# the eligibility admits (R=1024: a 4 MiB [R, R] int32 output block and
# its accumulator beside the double-buffered input block and the int8
# unpack) needs 20 MiB — the chip's compiler refused it at the default
# (tools/kernel_census.py, PR 21).
_GRAM_PALLAS_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 << 20)


def _bit_slabs(blk):
    """[R, wb] uint32 -> [R, wb*32] int8 0/1 inside a Pallas kernel:
    32 shift/mask slabs concatenated along the lane axis.  The self- and
    cross-gram kernels MUST share this (their column permutations have
    to agree with each other and be self-consistent for the gram)."""
    return jnp.concatenate(
        [
            ((blk >> jnp.uint32(k)) & jnp.uint32(1)).astype(jnp.int8)
            for k in range(32)
        ],
        axis=1,
    )


def _gram_pallas_kernel(in_ref, out_ref):
    """One [SB, R, WB] step of the self-gram: unpack each shard's word
    block to int8 bit slabs IN VMEM and feed the MXU.  The XLA scan
    materializes the 32x int8 expansion through HBM, which bounds it at
    ~2x the fused launch time (measured 33 vs 18 ms on a 10.7e9-bit
    index on one v5e chip; the remaining floor is the VPU unpack
    itself)."""
    s = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when((s == 0) & (w == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jnp.zeros(out_ref.shape, jnp.int32)
    for si in range(in_ref.shape[0]):
        x = _bit_slabs(in_ref[si])  # [R, WB*32] 0/1
        acc = acc + lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    out_ref[...] += acc


def _gram_pallas_sb(S: int) -> int:
    """Shards per grid step: the largest divisor of S up to
    _GRAM_PALLAS_SB — a non-dividing block would force a full
    index-sized jnp.pad copy per launch (measured: sb in 1..8 performs
    identically; the scan is unpack-bound)."""
    for sb in range(min(_GRAM_PALLAS_SB, S), 0, -1):
        if S % sb == 0:
            return sb
    return 1


@partial(jax.jit, static_argnames=("sb", "wb"))
def _gram_matrix_pallas(bits: jax.Array, *, sb: int, wb: int) -> jax.Array:
    S, R, W = bits.shape
    assert S % sb == 0, (S, sb)  # use _gram_pallas_sb; a non-dividing
    return pl.pallas_call(       # block would silently drop shards
        _gram_pallas_kernel,
        grid=(S // sb, W // wb),
        in_specs=[pl.BlockSpec((sb, R, wb), lambda s, w: (s, 0, w))],
        out_specs=pl.BlockSpec((R, R), lambda s, w: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((R, R), jnp.int32),
        compiler_params=_GRAM_PALLAS_PARAMS,
        interpret=_interpret(),
    )(bits)


# The fused grams get their OWN gates, default ON on TPU: unlike the
# scan kernels (where fused XLA wins), they measure ~1.7-1.8x faster
# than the XLA grams.  PILOSA_TPU_NO_PALLAS_GRAM=1 reverts to XLA.
# One gate PER KERNEL: the self- and cross-gram are distinct Mosaic
# programs, so one kernel's probe result must neither vouch for nor
# condemn the other.


class _PallasGate:
    """Tri-state probe flag for one Pallas kernel family: None =
    unproven, True = proven good, False = demoted.  Past the probe,
    demotion requires MAX_FAILS LIFETIME failures — one transient
    (device OOM under load) must not disable a proven kernel, while a
    persistently broken cached program must not be re-attempted
    forever; the counter is deliberately never reset on success, so a
    healthy sibling program sharing the gate cannot starve a broken
    one's demotion."""

    __slots__ = ("ok", "fails")
    MAX_FAILS = 3

    def __init__(self):
        self.ok: bool | None = None
        self.fails = 0  # lifetime count — NOT reset on success: a gate
        # may serve several compiled programs, and a healthy one's
        # successes must not starve a broken sibling's demotion


_self_gram_gate = _PallasGate()
_cross_gram_gate = _PallasGate()


def _gram_pallas_wb(R: int, W: int) -> int:
    """The fused gram's word block for an R-row stack, or 0 when the
    kernel should not engage.  The VMEM cap must be floored to a power
    of two BEFORE _word_block halves it into W — a non-power-of-two cap
    (any non-power-of-two R) would collapse wb to 1-2 and silently
    disable the kernel."""
    cap = _GRAM_PALLAS_UNPACK_BYTES // (32 * max(R, 1))
    if cap < 1 or R < 8:
        return 0
    wb = _word_block(W, 1 << (cap.bit_length() - 1))
    return wb if wb >= 128 else 0  # lane-width floor: tiny blocks don't tile


def _gram_pallas_eligible(R: int, W: int, gate=None) -> bool:
    gate = gate or _self_gram_gate
    return (
        gate.ok is not False
        and jax.default_backend() == "tpu"
        and os.environ.get("PILOSA_TPU_NO_PALLAS_GRAM") != "1"
        and _gram_pallas_wb(R, W) > 0
    )


def gram_matrix_traced(bits: jax.Array) -> jax.Array:
    """Trace-safe gram chooser for callers embedding the gram inside
    their OWN jit (e.g. fusing a transform into the input, or a
    shard_map's per-device block): picks the fused Pallas kernel by
    static shape/backend with no runtime fallback.  Use
    :func:`gram_matrix` outside jit."""
    _, R, W = bits.shape
    if _gram_pallas_eligible(R, W):
        return _gram_matrix_pallas(
            bits, sb=_gram_pallas_sb(bits.shape[0]), wb=_gram_pallas_wb(R, W)
        )
    return gram_matrix_xla(bits)


def _with_gram_fallback(
    pallas_fn, fallback_fn, gate=None, kernel="gram", deferred=False
):
    """The gram family's shared probe/demote contract: the first success
    proves the gate; every failure — probe-time or proven — is answered
    by ``fallback_fn``, counted visibly, and charged against
    _PallasGate.MAX_FAILS LIFETIME failures before demotion (never reset
    on success — a healthy sibling program sharing the gate must not
    starve a broken one's demotion).  Probe-time failures get the same
    tolerance as proven-kernel failures: one device-OOM blip on the
    first-ever call must not silently lose the fused path for the
    process lifetime, while a genuinely broken kernel (compile error)
    still demotes after MAX_FAILS bounded re-probes.

    ``deferred`` is for the caller that pulls later and answers a failed
    pull itself (the GroupBy lane re-runs the call on the per-call path,
    which comes through here undeferred): a PROVEN kernel is then
    enqueued and not awaited.  An unproven one is probed as ever."""
    gate = gate or _self_gram_gate
    try:
        # synchronize INSIDE the try: async dispatch would let a
        # runtime failure (e.g. device OOM) surface at the caller's
        # np.asarray instead of being re-answered by the fallback
        t0 = time.perf_counter()
        with enqueue(kernel):
            out = pallas_fn()
        if not (deferred and gate.ok):
            out = wait(out, kernel)
        if gate.ok is None:
            gate.ok = True
        _note_dispatch(kernel, "pallas", wall=time.perf_counter() - t0)
        return out
    except Exception as exc:
        # a failing PROBE degrades a default-ON fast path: every attempt
        # is logged as an error (with the compiler's message) so the
        # resulting latency is diagnosable
        _note_pallas_fallback(exc, kernel, probing=gate.ok is None)
        gate.fails += 1
        if gate.fails >= gate.MAX_FAILS:
            gate.ok = False
            logger.error(
                "pallas %s family disabled after %d failures",
                kernel, gate.fails,
            )
        with enqueue(kernel) as sp:
            out = fallback_fn()
        _note_dispatch(kernel, "xla", wall=sp.duration, demoted=True)
        return out


def gram_matrix(bits: jax.Array) -> jax.Array:
    """Self-gram dispatcher: fused-unpack Pallas kernel on TPU, XLA scan
    otherwise or on any Pallas failure."""
    _, R, W = bits.shape
    if _multi_device(bits) or not _gram_pallas_eligible(R, W):
        return _timed_xla("gram_matrix", gram_matrix_xla, bits)
    return _with_gram_fallback(
        lambda: gram_matrix_traced(bits),
        lambda: gram_matrix_xla(bits),
        kernel="gram_matrix",
    )


@jax.jit
def gram_matrix_xla(bits: jax.Array) -> jax.Array:
    """``G[i, j] = sum_s popcount(bits[s, i] & bits[s, j])`` for ALL row
    pairs, as one scan of the index with an int8 matmul per word block on
    the MXU (0/1 dot product == AND+popcount).

    Kept separate from :func:`cross_gram_xla` deliberately: the self-gram
    unpacks each block ONCE (cross would unpack both operands), and this
    is the hottest serving kernel.

    int32 accumulation: per-block partials are <= wb*32 and callers
    (:func:`pair_gram`) chunk the shard axis so S * W * 32 < 2^31 —
    int64 cannot be used here because without ``jax_enable_x64`` JAX
    silently narrows it back to int32."""
    _, R, W = bits.shape
    blocks = _gram_blocks(bits, _gram_word_block(W))

    def body(acc, blk):  # blk: [R, wb] uint32
        x = _unpack_int8(blk)
        g = lax.dot_general(
            x, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc + g, None

    acc0 = jnp.zeros((R, R), jnp.int32)
    acc, _ = lax.scan(body, acc0, blocks)
    return acc


@jax.jit
def gram_gather_xla(bits: jax.Array, idx: jax.Array) -> jax.Array:
    """Gram over the row subset ``bits[:, idx]`` — the batch's distinct
    rows only, so the scan reads U/R of the index."""
    return gram_matrix_xla(bits[:, idx])


@jax.jit
def _gram_gather_fused(bits: jax.Array, idx: jax.Array) -> jax.Array:
    # gather fused into the same program as the kernel (mirrors
    # _cross_gram_gather_fused: the eager form would materialize the
    # gathered copy as a standalone dispatch)
    return gram_matrix_traced(bits[:, idx])


def gram_gather(bits: jax.Array, idx: jax.Array) -> jax.Array:
    """Subset-gram dispatcher: gather+fused Pallas gram in one program
    when eligible (the in-program gather is far cheaper than the XLA
    scan's per-block int8 expansion), else the fused XLA scan."""
    U = int(idx.shape[0])
    _, _, W = bits.shape
    if not _multi_device(bits) and _gram_pallas_eligible(U, W):
        return _with_gram_fallback(
            lambda: _gram_gather_fused(bits, idx),
            lambda: gram_gather_xla(bits, idx),
            kernel="gram_gather",
        )
    return _timed_xla("gram_gather", gram_gather_xla, bits, idx)


# Largest pair total an int32 gram accumulator may reach (tests shrink it
# to exercise the chunked path on small shapes).
_GRAM_ACC_LIMIT = 2**31 - 1


def _gram_int32_safe(s: int, w: int) -> bool:
    """A pair's total fits int32 while S * W * 32 <= the limit."""
    return s * w * 32 <= _GRAM_ACC_LIMIT


def row_counts_supported(bits) -> bool:
    """Whether ``row_counts`` can serve this stack — always, except a
    process-spanning mesh so large that even a single-shard-per-device
    psum slice would overflow int32 (callers decline to per-fragment
    counting instead of catching row_counts' ValueError)."""
    m = shards_axis_of(bits)
    if m is None or not mesh_spans_processes(m[0]):
        return True
    S, _, W = bits.shape
    return _gram_int32_safe(S, W) or _psum_chunk_size(m[0], W) >= 1


def stack_spans_processes(x) -> bool:
    """Whether ``x`` is a shards-sharded stack whose mesh includes other
    processes' devices.  The decline guard for the remaining batched
    paths whose kernels return per-shard partials (not host addressable
    there) — the compiled-AST BITMAP programs (host-side Row segments)
    and the k-level GroupBy combo engine; pair/masked/row counts, the
    grams, and the compiled-AST COUNT programs now reduce in-program
    (psum) on spanning meshes instead of declining."""
    m = shards_axis_of(x)
    return m is not None and mesh_spans_processes(m[0])


@lru_cache(maxsize=64)
def mesh_spans_processes(mesh) -> bool:
    """Whether the mesh includes devices owned by other processes — the
    multi-host serving layout, where per-device partials are NOT host
    addressable and the reduce must happen in-program.  Cached: the
    answer is constant per mesh and this sits on ~0.1 ms serving
    paths."""
    me = jax.process_index()
    return any(d.process_index != me for d in mesh.devices.flat)


@lru_cache(maxsize=64)
def _gram_mesh_fn(mesh, axis, gather, in_program_reduce, use_pallas=False):
    """jit(shard_map) gram over a shards-sharded stack.  Two reduce
    modes: per-device partials stacked along the mesh axis for a
    host-side int64 sum (single-host serving), or an IN-PROGRAM
    ``lax.psum`` whose reduce rides the runtime's collectives (ICI
    within a host, DCN across — SURVEY §2.4's mapping of the
    reference's mapReduce reduce step, executor.go:2454) and whose
    result is replicated on every process — required when the mesh
    spans processes, where stacked partials would not be host
    addressable.  ``use_pallas`` routes each device's block through the
    fused-unpack gram (gram_matrix_traced picks it by static shape; it
    compiles and matches XLA on a four-chip v5e host,
    tools/kernel_census.py); the psum path stays XLA-only — Pallas
    composed with a cross-process collective has never run on a
    multi-host slice."""
    if gather:
        if use_pallas:
            base = lambda b, i: gram_matrix_traced(b[:, i])
        else:
            base = lambda b, i: gram_gather_xla(b, i)
        in_specs = (P(axis, None, None), P(None))
    else:
        if use_pallas:
            base = lambda b: gram_matrix_traced(b)
        else:
            base = lambda b: gram_matrix_xla(b)
        in_specs = (P(axis, None, None),)
    if in_program_reduce:
        local = lambda *a: lax.psum(base(*a), axis)
        out_specs = P(None, None)
    else:
        local = lambda *a: base(*a)[None]
        out_specs = P(axis, None, None)
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            # the gram scan's zero-init carry is replicated while the
            # shard blocks vary per device; the accumulation is still
            # purely local so the vma check is safe to relax
            check_vma=False,
        )
    )


def _carry_psum_chunks(local_partial, arrs, axis, chunk):
    """In-program exact accumulation past int32: loop the device-local
    shard block in ``chunk``-shard slices, psum each slice's int32
    partial across the mesh axis, and accumulate into a (hi, lo) uint32
    carry-save pair (device int64 is unavailable without x64).  The
    caller picks ``chunk`` so one slice's GLOBAL psum total is
    int32-exact."""
    s_loc = arrs[0].shape[0]
    n_chunks = -(-s_loc // chunk)
    pad = n_chunks * chunk - s_loc
    arrs = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in arrs
    )
    shape = jax.eval_shape(
        local_partial,
        *(
            jax.ShapeDtypeStruct((chunk,) + a.shape[1:], a.dtype)
            for a in arrs
        ),
    ).shape

    def body(i, acc):
        hi, lo = acc
        blks = tuple(
            lax.dynamic_slice_in_dim(a, i * chunk, chunk, 0) for a in arrs
        )
        p = lax.psum(local_partial(*blks), axis).astype(jnp.uint32)
        new_lo = lo + p
        # p < 2^32, so the add wrapped iff the result went down
        hi = hi + (new_lo < lo).astype(jnp.uint32)
        return hi, new_lo

    z = jnp.zeros(shape, jnp.uint32)
    return lax.fori_loop(0, n_chunks, body, (z, z))


@lru_cache(maxsize=64)
def _psum_chunked_fn(mesh, axis, kind, chunk):
    """jit(shard_map) for process-spanning meshes whose totals exceed
    int32: returns replicated (hi, lo) uint32 arrays to combine on host
    as hi * 2^32 + lo."""
    if kind == "gram":
        local = lambda b: _carry_psum_chunks(
            gram_matrix_xla, (b,), axis, chunk
        )
        in_specs = (P(axis, None, None),)
        out = P(None, None)
    elif kind == "gram_gather":
        local = lambda b, i: _carry_psum_chunks(
            lambda blk: gram_gather_xla(blk, i), (b,), axis, chunk
        )
        in_specs = (P(axis, None, None), P(None))
        out = P(None, None)
    elif kind == "cross":
        local = lambda a, b, ia, ib: _carry_psum_chunks(
            lambda x, y: cross_gram_xla(x[:, ia], y[:, ib]),
            (a, b),
            axis,
            chunk,
        )
        in_specs = (
            P(axis, None, None), P(axis, None, None), P(None), P(None)
        )
        out = P(None, None)
    elif kind.startswith("pair2:"):
        op = kind.split(":", 1)[1]
        local = lambda a, b, ra, rb: _carry_psum_chunks(
            lambda x, y: jnp.sum(
                pair_count_two_batched_xla(x, y, ra, rb, op=op), axis=1
            ),
            (a, b),
            axis,
            chunk,
        )
        in_specs = (
            P(axis, None, None), P(axis, None, None), P(None), P(None)
        )
        out = P(None)
    elif kind.startswith("pair:"):
        op = kind.split(":", 1)[1]
        local = lambda b, ra, rb: _carry_psum_chunks(
            lambda x: jnp.sum(
                pair_count_batched_xla(x, ra, rb, op=op), axis=1
            ),
            (b,),
            axis,
            chunk,
        )
        in_specs = (P(axis, None, None), P(None), P(None))
        out = P(None)
    elif kind == "masked_rows":
        local = lambda b, f: _carry_psum_chunks(
            lambda x, ff: jnp.sum(masked_row_counts_xla(x, ff), axis=0),
            (b, f),
            axis,
            chunk,
        )
        in_specs = (P(axis, None, None), P(axis, None))
        out = P(None)
    else:  # rows
        local = lambda b: _carry_psum_chunks(
            row_counts_xla, (b,), axis, chunk
        )
        in_specs = (P(axis, None, None),)
        out = P(None)
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(out, out),
            check_vma=False,
        )
    )


def _psum_chunk_size(mesh, w: int) -> int:
    """Per-device shards per chunked psum so one slice's global total
    stays int32-exact; 0 when even a single shard per device overflows
    (callers decline)."""
    return _GRAM_ACC_LIMIT // max(1, mesh.devices.size * w * 32)


def _hi_lo_total(hi, lo) -> np.ndarray:
    return pull(hi).astype(np.int64) * 2**32 + pull(lo).astype(np.int64)


def pair_gram(bits: jax.Array, row_idx) -> np.ndarray | None:
    """``int64 numpy [U, U]`` intersection counts between every pair of
    the rows named by ``row_idx``, summed over all shards — the
    one-launch answer to a whole batch of pair-count queries
    (reference executor.go:653-680 + roaring.go:568, re-shaped for the
    MXU).  None when ``row_idx`` is too wide for the gram path
    (> GRAM_MAX_ROWS); callers fall back to the scan kernels, which
    serve process-spanning meshes too via in-program psum (replicated
    int64 totals instead of per-shard partials — kernels.py r05).

    Works on single-device and shards-axis NamedSharding'd stacks; on a
    single-host mesh each device grams its local shard block and the
    host reduces, while a process-spanning mesh reduces in-program
    (psum, carry-save chunked past int32).
    """
    S, R, W = bits.shape
    U = len(row_idx)
    if U == 0 or U > GRAM_MAX_ROWS:
        return None
    full = U == R and list(row_idx) == list(range(R))
    if not full:
        # pad the gather to a power of two (repeating row 0) so jit
        # programs are reused as the batch's distinct-row count drifts
        Up = pow2_pad_len(U)
        idx = np.zeros(Up, np.int32)
        idx[:U] = row_idx
        if Up > U:
            # padded vs useful gather-subset bytes ([S, Up, W] uint32)
            note_pad("pair_gram", S * Up * W * 4, S * U * W * 4)
    m = shards_axis_of(bits)
    if m is not None:
        mesh, axis = m
        if mesh_spans_processes(mesh):
            # multi-host stack: reduce in-program (psum over DCN/ICI) —
            # per-device partials aren't host addressable here
            if _gram_int32_safe(S, W):
                fn = _gram_mesh_fn(mesh, axis, not full, True)
                out = fn(bits) if full else fn(bits, jnp.asarray(idx))
                return pull(out).astype(np.int64)[:U, :U]
            chunk = _psum_chunk_size(mesh, W)
            if chunk < 1:
                return None
            fn = _psum_chunked_fn(
                mesh, axis, "gram_gather" if not full else "gram", chunk
            )
            hi, lo = fn(bits) if full else fn(bits, jnp.asarray(idx))
            return _hi_lo_total(hi, lo)[:U, :U]
        if not _gram_int32_safe(-(-S // mesh.devices.size), W):
            # a device-local partial could wrap int32; callers fall back
            # to the scan kernels' [B, S] per-shard partials
            return None
        # eligibility must consider the shape the per-device base will
        # actually see (the padded gather subset, not the stack's R) —
        # a True-variant program that would trace to pure XLA anyway
        # must not own the Pallas gate's failure semantics
        use_p = _gram_pallas_eligible(R if full else len(idx), W)

        idx_d = None if full else h2d(idx)

        def _run(with_pallas: bool):
            fn = _gram_mesh_fn(mesh, axis, not full, False, with_pallas)
            return fn(bits) if full else fn(bits, idx_d)

        if use_p:
            out = _with_gram_fallback(
                lambda: _run(True), lambda: _run(False), kernel="pair_gram"
            )
        else:
            with enqueue("pair_gram") as sp:
                out = _run(False)
            _note_dispatch("pair_gram", "xla", wall=sp.duration, args=(bits,))
        return pull(out, "pair_gram").astype(np.int64).sum(axis=0)[:U, :U]
    if _gram_int32_safe(S, W):
        if full:
            out = gram_matrix(bits)
        else:
            out = gram_gather(bits, h2d(idx))
        return pull(out, "pair_gram").astype(np.int64)[:U, :U]
    # Giant single-device index: chunk the shard axis so each chunk's
    # partial gram is int32-exact, and sum the chunks in host int64
    # (int64 on device is unavailable without jax_enable_x64).
    chunk = max(1, _GRAM_ACC_LIMIT // (W * 32))
    total = np.zeros((U, U) if full else (len(idx), len(idx)), np.int64)
    for c0 in range(0, S, chunk):
        blk = bits[c0 : c0 + chunk]
        out = gram_matrix(blk) if full else gram_gather(
            blk, jnp.asarray(idx)
        )
        total += pull(out).astype(np.int64)
    return total[:U, :U]


def pair_counts_from_gram(
    gram: np.ndarray, pa: np.ndarray, pb: np.ndarray, op: str
) -> np.ndarray:
    """Evaluate a batch of pair-op counts from gram entries.  ``pa/pb``
    index into the gram's row-subset coordinates."""
    g = gram[pa, pb]
    if op == "intersect":
        return g
    da = gram[pa, pa]
    if op == "difference":
        return da - g
    db = gram[pb, pb]
    if op == "union":
        return da + db - g
    if op == "xor":
        return da + db - 2 * g
    raise ValueError(f"unknown pair op: {op}")


@jax.jit
def cross_gram_xla(bits_a: jax.Array, bits_b: jax.Array) -> jax.Array:
    """``G[i, j] = sum_s popcount(bits_a[s, i] & bits_b[s, j])`` for ALL
    cross-field row pairs — the 2-level GroupBy combination matrix
    (reference executor.go:3208-3211 counts the intersection of the last
    two levels per combination; one MXU scan answers every combination).
    int32 accumulation; callers chunk shards via :func:`cross_pair_gram`.
    """
    S, Ra, W = bits_a.shape
    Rb = bits_b.shape[1]
    wb = _gram_word_block(W)
    blocks_a = _gram_blocks(bits_a, wb)
    blocks_b = _gram_blocks(bits_b, wb)

    def body(acc, blk):
        ba, bb = blk
        xa = _unpack_int8(ba)
        xb = _unpack_int8(bb)
        g = lax.dot_general(
            xa, xb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return acc + g, None

    acc0 = jnp.zeros((Ra, Rb), jnp.int32)
    acc, _ = lax.scan(body, acc0, (blocks_a, blocks_b))
    return acc


@jax.jit
def cross_gram_gather_xla(
    bits_a: jax.Array, bits_b: jax.Array, ia: jax.Array, ib: jax.Array
) -> jax.Array:
    """Cross gram over row subsets, gathered inside the program."""
    return cross_gram_xla(bits_a[:, ia], bits_b[:, ib])


def _cross_gram_pallas_kernel(a_ref, b_ref, out_ref):
    """Fused-unpack cross gram — both operands' word blocks unpack to
    int8 bit slabs in VMEM (same bottleneck analysis as
    _gram_pallas_kernel; the cross variant pays the VPU unpack twice)."""
    s = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when((s == 0) & (w == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jnp.zeros(out_ref.shape, jnp.int32)
    for si in range(a_ref.shape[0]):
        acc = acc + lax.dot_general(
            _bit_slabs(a_ref[si]),
            _bit_slabs(b_ref[si]),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
    out_ref[...] += acc


@partial(jax.jit, static_argnames=("sb", "wb"))
def _cross_gram_pallas(
    bits_a: jax.Array, bits_b: jax.Array, *, sb: int, wb: int
) -> jax.Array:
    S, Ra, W = bits_a.shape
    Rb = bits_b.shape[1]
    assert S % sb == 0, (S, sb)  # see _gram_matrix_pallas
    return pl.pallas_call(
        _cross_gram_pallas_kernel,
        grid=(S // sb, W // wb),
        in_specs=[
            pl.BlockSpec((sb, Ra, wb), lambda s, w: (s, 0, w)),
            pl.BlockSpec((sb, Rb, wb), lambda s, w: (s, 0, w)),
        ],
        out_specs=pl.BlockSpec((Ra, Rb), lambda s, w: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((Ra, Rb), jnp.int32),
        compiler_params=_GRAM_PALLAS_PARAMS,
        interpret=_interpret(),
    )(bits_a, bits_b)


def _cross_pallas_engages(Ra: int, Rb: int, W: int) -> bool:
    """The ONE cross-gram Pallas predicate — cross_gram_traced and every
    call site that wraps it in _with_gram_fallback must share it, or a
    desynced gate would let a quietly-XLA trace falsely prove the
    Pallas gate.  Both operands' unpacked slabs share the VMEM budget,
    so eligibility uses Ra + Rb."""
    return (
        Ra >= 8
        and Rb >= 8
        and _gram_pallas_eligible(Ra + Rb, W, gate=_cross_gram_gate)
    )


def cross_gram_traced(bits_a: jax.Array, bits_b: jax.Array) -> jax.Array:
    """Trace-safe cross-gram chooser (see gram_matrix_traced)."""
    _, Ra, W = bits_a.shape
    Rb = bits_b.shape[1]
    if _cross_pallas_engages(Ra, Rb, W):
        return _cross_gram_pallas(
            bits_a,
            bits_b,
            sb=_gram_pallas_sb(bits_a.shape[0]),
            wb=_gram_pallas_wb(Ra + Rb, W),
        )
    return cross_gram_xla(bits_a, bits_b)


@jax.jit
def _cross_gram_gather_fused(
    bits_a: jax.Array, bits_b: jax.Array, ia: jax.Array, ib: jax.Array
) -> jax.Array:
    # gather fused into the same program as the kernel (the eager form
    # would materialize the gathered copies as standalone dispatches)
    return cross_gram_traced(bits_a[:, ia], bits_b[:, ib])


def cross_gram_gather(
    bits_a: jax.Array, bits_b: jax.Array, ia: jax.Array, ib: jax.Array
) -> jax.Array:
    """Subset cross-gram dispatcher with the gram family's runtime
    fallback semantics."""
    _, _, W = bits_a.shape
    Ua, Ub = int(ia.shape[0]), int(ib.shape[0])
    if (
        _multi_device(bits_a)
        or _multi_device(bits_b)
        or not _cross_pallas_engages(Ua, Ub, W)
    ):
        with enqueue("cross_gram_gather") as sp:
            out = cross_gram_gather_xla(bits_a, bits_b, ia, ib)
        _note_dispatch(
            "cross_gram_gather", "xla", wall=sp.duration, args=(bits_a, ia, ib)
        )
        return out
    return _with_gram_fallback(
        lambda: _cross_gram_gather_fused(bits_a, bits_b, ia, ib),
        lambda: cross_gram_gather_xla(bits_a, bits_b, ia, ib),
        gate=_cross_gram_gate,
        kernel="cross_gram_gather",
    )


@lru_cache(maxsize=64)
def _cross_gram_mesh_fn(mesh, axis, in_program_reduce):
    """Cross gram over aligned shards-sharded stacks — stacked partials
    for a host-side sum, or an in-program psum reduce for
    process-spanning meshes (same two modes as _gram_mesh_fn)."""
    base = lambda a, b, ia, ib: cross_gram_xla(a[:, ia], b[:, ib])
    if in_program_reduce:
        local = lambda *args: lax.psum(base(*args), axis)
        out_specs = P(None, None)
    else:
        local = lambda *args: base(*args)[None]
        out_specs = P(axis, None, None)
    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(
                P(axis, None, None), P(axis, None, None), P(None), P(None)
            ),
            out_specs=out_specs,
            check_vma=False,  # same local-accumulation argument as
        )  # _gram_mesh_fn
    )


def _cross_gram_sharded_fn(mesh, axis):
    return _cross_gram_mesh_fn(mesh, axis, False)


def _cross_gram_psum_fn(mesh, axis):
    return _cross_gram_mesh_fn(mesh, axis, True)


def cross_pair_gram(bits_a: jax.Array, bits_b: jax.Array, idx_a, idx_b):
    """``int64 numpy [Ua, Ub]`` cross-field intersection counts between
    the named row subsets, summed over all shards; None when a subset is
    too wide (callers fall back to the batched scan kernels).  Both
    stacks must share the (aligned, equally-sharded) shard axis."""
    S, _, W = bits_a.shape
    Ua, Ub = len(idx_a), len(idx_b)
    if Ua == 0 or Ub == 0 or max(Ua, Ub) > GRAM_MAX_ROWS:
        return None
    # pad gathers to powers of two for program reuse
    ia = np.zeros(pow2_pad_len(Ua), np.int32)
    ia[:Ua] = idx_a
    ib = np.zeros(pow2_pad_len(Ub), np.int32)
    ib[:Ub] = idx_b
    if len(ia) > Ua or len(ib) > Ub:
        note_pad(
            "cross_pair_gram",
            S * (len(ia) + len(ib)) * W * 4,
            S * (Ua + Ub) * W * 4,
        )
    m = shards_axis_of(bits_a)
    if m is not None and shards_axis_of(bits_b) == m:
        mesh, axis = m
        if mesh_spans_processes(mesh):
            # in-program psum reduce (see pair_gram's spanning branch)
            if _gram_int32_safe(S, W):
                out = _cross_gram_psum_fn(mesh, axis)(
                    bits_a, bits_b, jnp.asarray(ia), jnp.asarray(ib)
                )
                return pull(out).astype(np.int64)[:Ua, :Ub]
            chunk = _psum_chunk_size(mesh, W)
            if chunk < 1:
                return None
            hi, lo = _psum_chunked_fn(mesh, axis, "cross", chunk)(
                bits_a, bits_b, jnp.asarray(ia), jnp.asarray(ib)
            )
            return _hi_lo_total(hi, lo)[:Ua, :Ub]
        if not _gram_int32_safe(-(-S // mesh.devices.size), W):
            return None
        ia_d, ib_d = h2d(ia), h2d(ib)
        with enqueue("cross_pair_gram"):
            out = _cross_gram_sharded_fn(mesh, axis)(bits_a, bits_b, ia_d, ib_d)
        return pull(out, "cross_pair_gram").astype(np.int64).sum(axis=0)[
            :Ua, :Ub
        ]
    if m is not None or shards_axis_of(bits_b) is not None:
        return None  # mismatched shardings; scan kernels handle it
    ia_d, ib_d = h2d(ia), h2d(ib)
    if _gram_int32_safe(S, W):
        out = cross_gram_gather(bits_a, bits_b, ia_d, ib_d)
        return pull(out, "cross_gram_gather").astype(np.int64)[:Ua, :Ub]
    chunk = max(1, _GRAM_ACC_LIMIT // (W * 32))
    total = np.zeros((len(ia), len(ib)), np.int64)
    for c0 in range(0, S, chunk):
        out = cross_gram_gather(
            bits_a[c0 : c0 + chunk], bits_b[c0 : c0 + chunk], ia_d, ib_d
        )
        total += pull(out).astype(np.int64)
    return total[:Ua, :Ub]


# ---------------------------------------------------------------------------
# Two-tensor pair count: Count(op(A.Row(ra[i]), B.Row(rb[i])))  (GroupBy)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("op",))
def pair_count_two_batched_xla(
    bits_a: jax.Array, bits_b: jax.Array, ras: jax.Array, rbs: jax.Array,
    *, op: str = "intersect",
) -> jax.Array:
    def body(_, q):
        ra, rb = q
        words = _OPS[op](bits_a[:, ra], bits_b[:, rb])
        return None, jnp.sum(
            lax.population_count(words).astype(jnp.int32), axis=-1
        )

    _, counts = lax.scan(body, None, (ras, rbs))
    return counts


def pair_count_two_batched(
    bits_a: jax.Array, bits_b: jax.Array, ras: jax.Array, rbs: jax.Array,
    *, op: str = "intersect",
):
    """Cross-tensor pair counts; same return contract as
    ``pair_count_batched``: device ``int32[B, S]`` partials on local
    stacks, replicated ``np.int64[B]`` in-program psum totals on a
    process-spanning mesh."""
    m = shards_axis_of(bits_a)
    if m is not None and shards_axis_of(bits_b) == m:
        mesh, axis = m
        if mesh_spans_processes(mesh):
            _, _, W = bits_a.shape
            chunk = _psum_chunk_size(mesh, W)
            if chunk < 1:
                raise ValueError(
                    "pair totals exceed int32 even per single psum"
                    " slice; shrink the shard width or the per-host mesh"
                )
            hi, lo = _psum_chunked_fn(mesh, axis, "pair2:" + op, chunk)(
                bits_a, bits_b, ras, rbs
            )
            out = _hi_lo_total(hi, lo)
            _note_dispatch("pair_count_two", "xla", args=(bits_a, ras))
            return out
        with enqueue("pair_count_two") as sp:
            out = _pair_count_sharded_fn(mesh, axis, op, True)(
                bits_a, bits_b, ras, rbs
            )
        _note_dispatch(
            "pair_count_two", "xla", wall=sp.duration, args=(bits_a, ras)
        )
        return out
    with enqueue("pair_count_two") as sp:
        out = pair_count_two_batched_xla(bits_a, bits_b, ras, rbs, op=op)
    _note_dispatch(
        "pair_count_two", "xla", wall=sp.duration, args=(bits_a, ras)
    )
    return out


# ---------------------------------------------------------------------------
# Row-scan popcount: counts[r] = sum_s sum_w popcount(bits[s, r, w])
# ---------------------------------------------------------------------------


@jax.jit
def row_counts_xla(bits: jax.Array) -> jax.Array:
    return jnp.sum(lax.population_count(bits).astype(jnp.int32), axis=(0, 2))


@jax.jit
def row_counts_per_shard_xla(bits: jax.Array) -> jax.Array:
    return jnp.sum(lax.population_count(bits).astype(jnp.int32), axis=2)


# ---------------------------------------------------------------------------
# GroupBy combo kernels: iterated batched intersect-counts over a running
# set of prefix masks (reference executor.go:3057-3230 runs one
# intersectionCount per combination; here one launch per LEVEL).
# ---------------------------------------------------------------------------


@jax.jit
def combo_counts(prefix: jax.Array, bits: jax.Array, idx: jax.Array) -> jax.Array:
    """``int32[C, Rl, S]`` per-shard counts of every (prefix combo, row)
    intersection: popcount(prefix[c] & bits[:, idx[r]]).  A scan over the
    level's rows keeps peak memory at one [C, S, W] intermediate."""

    def body(_, r):
        rowsl = bits[:, r]  # [S, W]
        return None, jnp.sum(
            lax.population_count(prefix & rowsl[None]).astype(jnp.int32),
            axis=-1,
        )  # [C, S]

    _, out = lax.scan(body, None, idx)  # [Rl, C, S]
    return jnp.transpose(out, (1, 0, 2))


@jax.jit
def _combo_gram_xla(prefix: jax.Array, bits: jax.Array, idx: jax.Array):
    return cross_gram_xla(jnp.transpose(prefix, (1, 0, 2)), bits[:, idx])


@jax.jit
def _combo_gram_fused(prefix: jax.Array, bits: jax.Array, idx: jax.Array):
    # trace-time chooser: Pallas when the gate/shape allow (the caller
    # guards with _gram_pallas_eligible and _with_gram_fallback)
    return cross_gram_traced(jnp.transpose(prefix, (1, 0, 2)), bits[:, idx])


def combo_counts_gram(
    prefix: jax.Array, bits: jax.Array, idx, deferred: bool = False
):
    """``int32[C, Rl]`` totals of every (prefix combo, row) intersection
    as ONE cross gram on the MXU — the k-level GroupBy's per-level count
    (reference executor.go:3208-3211), reading the prefix masks once
    instead of once per row.  The launch only: the device's array,
    enqueued and not pulled (``deferred``: see
    :func:`_with_gram_fallback`).  None when a total could wrap int32
    (S * W * 32 past the limit) or the level is too small for the unpack
    to pay off; callers fall back to :func:`combo_counts`."""
    C = prefix.shape[0]
    S, _, W = bits.shape
    if not _gram_int32_safe(S, W) or C * len(idx) < 32:
        return None
    if max(C, len(idx)) > GRAM_MAX_ROWS:
        # same cap as every gram wrapper: the per-step int8 unpack is
        # [C, wb*32] — a 65k-combo prefix would stage gigabytes where the
        # scan fallback peaks at one [C, S, W] intermediate
        return None
    if shards_axis_of(bits) is not None or _multi_device(prefix):
        # the gram scans over the SHARD axis, which would force GSPMD to
        # replicate prefix + stack onto every device; the scan kernels
        # iterate rows and partition cleanly, so decline
        return None
    idx_dev = h2d(idx, dtype=np.int32)
    # the shared predicate keeps this gate in lockstep with
    # cross_gram_traced (a desync would falsely prove the Pallas gate
    # from a quietly-XLA trace); a replicated multi-device stack (no
    # shards axis, >1 device) must keep the XLA path, which partitions
    # cleanly
    if not _multi_device(bits) and _cross_pallas_engages(C, len(idx), W):
        return _with_gram_fallback(
            lambda: _combo_gram_fused(prefix, bits, idx_dev),
            lambda: _combo_gram_xla(prefix, bits, idx_dev),
            gate=_cross_gram_gate,
            kernel="combo_gram",
            deferred=deferred,
        )
    return _timed_xla("combo_gram", _combo_gram_xla, prefix, bits, idx_dev)


@jax.jit
def refine_prefix(
    prefix: jax.Array, bits: jax.Array, cis: jax.Array, ris: jax.Array
) -> jax.Array:
    """Next level's surviving prefix masks:
    ``prefix[cis[i]] & bits[:, ris[i]]`` -> [C', S, W]."""
    return prefix[cis] & jnp.transpose(bits[:, ris], (1, 0, 2))


@jax.jit
def gather_prefix(bits: jax.Array, idx: jax.Array) -> jax.Array:
    """Level-0 prefix masks: rows of a stack as [C, S, W]."""
    return jnp.transpose(bits[:, idx], (1, 0, 2))


# ---------------------------------------------------------------------------
# Masked row-scan: counts[s, r] = sum_w popcount(bits[s, r, w] & filt[s, w])
# (filtered TopN: every row intersected with a source bitmap in one launch)
# ---------------------------------------------------------------------------


@jax.jit
def masked_row_counts_xla(bits: jax.Array, filt: jax.Array) -> jax.Array:
    return jnp.sum(
        lax.population_count(bits & filt[:, None, :]).astype(jnp.int32), axis=2
    )


@lru_cache(maxsize=64)
def _masked_row_counts_sharded_fn(mesh, axis):
    return jax.jit(
        shard_map(
            masked_row_counts_xla,
            mesh=mesh,
            in_specs=(P(axis, None, None), P(axis, None)),
            out_specs=P(axis, None),
        )
    )


def masked_row_counts(bits: jax.Array, filt: jax.Array):
    """``int64[R]`` numpy: per-row popcount of (row & filter) summed over
    shards.  One launch for every (shard, row) — kills the per-shard
    dispatch loop of filtered TopN."""
    m = shards_axis_of(bits)
    if m is not None:
        mesh, axis = m
        if mesh_spans_processes(mesh):
            # in-program psum (chunked hi/lo carry-save past int32):
            # filtered TopN stays on the fast lane across hosts
            _, _, W = bits.shape
            chunk = _psum_chunk_size(mesh, W)
            if chunk < 1:
                raise ValueError(
                    "masked row totals exceed int32 even per single"
                    " psum slice; shrink the shard width or the"
                    " per-host mesh"
                )
            fspec = NamedSharding(mesh, P(axis, None))
            if getattr(filt, "sharding", None) != fspec:
                filt = h2d(np.asarray(filt), fspec)
            hi, lo = _psum_chunked_fn(mesh, axis, "masked_rows", chunk)(
                bits, filt
            )
            return _hi_lo_total(hi, lo)
        fspec = NamedSharding(mesh, P(axis, None))
        if getattr(filt, "sharding", None) != fspec:
            filt = h2d(np.asarray(filt), fspec)
        partials = _timed_xla(
            "masked_row_counts",
            _masked_row_counts_sharded_fn(mesh, axis),
            bits,
            filt,
        )
    else:
        # a host filter goes into the call as it is: the jitted call's own
        # argument handling uploads it, inside kernels.enqueue
        partials = _timed_xla(
            "masked_row_counts", masked_row_counts_xla, bits, filt
        )
    return pull(partials, "masked_row_counts").astype(np.int64).sum(axis=0)


def _int32_safe(bits) -> bool:
    """Cross-shard per-row totals fit int32 when S * shard_bits < 2^31."""
    S, _, W = bits.shape
    return S * W * 32 < 2**31


def row_counts(bits: jax.Array):
    """Per-row popcounts over all shards.

    Returns an ``int32[R]`` device array on the fused path, or an
    ``int64[R]`` numpy array when cross-shard totals could overflow
    int32 or the stack is mesh-sharded (per-shard device partials summed
    host-side)."""
    m = shards_axis_of(bits)
    if m is not None:
        mesh, axis = m
        if mesh_spans_processes(mesh):
            S, _, W = bits.shape
            if _gram_int32_safe(S, W):
                out = _row_counts_mesh_fn(mesh, axis, True)(bits)
                return pull(out, "row_counts").astype(np.int64)
            chunk = _psum_chunk_size(mesh, W)
            if chunk < 1:
                raise ValueError(
                    "row totals exceed int32 even per single psum slice;"
                    " shrink the shard width or the per-host mesh"
                )
            hi, lo = _psum_chunked_fn(mesh, axis, "rows", chunk)(bits)
            return _hi_lo_total(hi, lo)
        partials = _timed_xla(
            "row_counts", _row_counts_mesh_fn(mesh, axis, False), bits
        )
        return pull(partials, "row_counts").astype(np.int64).sum(axis=0)
    if _int32_safe(bits):
        return _timed_xla("row_counts", row_counts_xla, bits)
    partials = _timed_xla(
        "row_counts_per_shard", row_counts_per_shard_xla, bits
    )
    return pull(partials, "row_counts").astype(np.int64).sum(axis=0)


@partial(jax.jit, static_argnames=("n",))
def _topn_xla(bits: jax.Array, *, n: int):
    return lax.top_k(row_counts_xla(bits), n)


def topn_counts(bits: jax.Array, n: int):
    """(top-n counts, row slots) fused with the row scan in one launch
    (reference fragment.go:1568-1700 TopN over the ranked cache). Falls
    back to host-side int64 selection when totals could overflow int32
    or the stack is mesh-sharded."""
    if shards_axis_of(bits) is None and _int32_safe(bits):
        return _timed_xla("topn", partial(_topn_xla, n=n), bits)
    counts = row_counts(bits)  # int64 numpy on this path
    n = min(n, counts.shape[0])
    slots = np.argsort(-counts, kind="stable")[:n]
    return counts[slots], slots
