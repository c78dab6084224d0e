"""Kernel census: every ``pl.pallas_call`` in ops/kernels.py, compiled for
the chip at its production shape and at the ends its eligibility admits,
run once and compared with its XLA twin.

    python -m tools.kernel_census

The serving dispatchers answer a refused Pallas kernel with the XLA twin
(ops/kernels.py ``_with_gram_fallback``), so on a serving node the
compiler's verdict is a log line and a counter.  Here the raw jitted
kernels are called with the block parameters the
dispatchers would pick, and a refusal is printed with the compiler's
message and fails the run.  ``chip_smoke.py`` runs this as its second
child, after the server has released the chip.

One line per (kernel, shape): ``compiled`` with compile+first-run
seconds, or ``REFUSED``/``MISMATCH`` with the reason.  The last line of
stdout is one JSON object.  Exit 0 only on a TPU with every case
compiled and equal to its twin; under an explicit ``JAX_PLATFORMS=cpu``
the kernels run in interpret mode at toy shapes as a rehearsal of this
script, which is reported as such and exits 3.
"""

from __future__ import annotations

import json
import sys
import time


def _cases(kernels, n_dev: int, production: bool):
    """(kernel, shape label, block params, needs, pallas thunk, xla
    thunk) tuples.  ``needs`` names the random operands: "a" a stack
    [S, Ra, W], "b" a second stack [S, Rb, W]."""
    k = kernels
    if production:
        S, W = 160, 32768  # BASELINE.json's 10.7e9-bit index at R=64
        r_max = 1024  # _gram_pallas_wb's lane floor: 4 MiB / (32 * R) >= 128
    else:
        S, W = 16, 256
        r_max = 64
    out = []

    def gram(S_, R_):
        sb, wb = k._gram_pallas_sb(S_), k._gram_pallas_wb(R_, W)
        assert wb, (R_, W)
        out.append((
            "gram_matrix", (S_, R_, W), f"sb={sb} wb={wb}", {"a": (S_, R_)},
            lambda a: k._gram_matrix_pallas(a, sb=sb, wb=wb),
            lambda a: k.gram_matrix_xla(a),
        ))

    # the serving stack of the smoke's field f, and a stack whose shard
    # count only 5 divides
    gram(S, 64)
    gram(5, 64)
    # row floor and a lone shard; the 32-row int8 tile; a row set a Set()
    # grew past the power of two; the widest stack the lane floor admits
    gram(S, 8)
    gram(1, 8)
    gram(S, 32)
    gram(S, 65)
    gram(8 if production else 2, r_max)

    def gram_gather(U):
        import numpy as np

        idx = np.arange(U, dtype=np.int32) * (64 // U)
        out.append((
            "gram_gather_fused", (S, 64, W), f"U={U}", {"a": (S, 64)},
            lambda a: k._gram_gather_fused(a, idx),
            lambda a: k.gram_gather_xla(a, idx),
        ))

    # what _batch_pair_counts compiles: the gather of the flight's
    # distinct rows (padded to a power of two) fused with the kernel
    gram_gather(8)
    gram_gather(32)

    def cross(S_, Ra, Rb):
        sb, wb = k._gram_pallas_sb(S_), k._gram_pallas_wb(Ra + Rb, W)
        assert wb and min(Ra, Rb) >= 8, (Ra, Rb, W)
        out.append((
            "cross_gram", (S_, Ra, Rb, W), f"sb={sb} wb={wb}",
            {"a": (S_, Ra), "b": (S_, Rb)},
            lambda a, b: k._cross_gram_pallas(a, b, sb=sb, wb=wb),
            lambda a, b: k.cross_gram_xla(a, b),
        ))

    # GroupBy(Rows(f), Rows(g)) of the smoke; both operands at the row
    # floor; a k-level combo level with a ragged prefix; the widest pair
    cross(S, 64, 8)
    cross(S, 8, 8)
    cross(1, 8, 8)
    cross(S, 24, 8)
    cross(8 if production else 2, r_max // 2, r_max // 2)

    if n_dev > 1:
        # the mesh lane pair_gram takes on a multi-chip host: Pallas per
        # device inside shard_map, partials stacked along the mesh axis
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.local_devices()), ("shards",))
        spec = NamedSharding(mesh, P("shards", None, None))
        idx = np.arange(16, dtype=np.int32) * 4
        out.append((
            "gram_mesh", (S, 64, W), f"devices={n_dev}", {"a": (S, 64)},
            lambda a: k._gram_mesh_fn(mesh, "shards", False, False, True)(
                jax.device_put(a, spec)
            ).sum(axis=0),
            lambda a: k.gram_matrix_xla(a),
        ))
        out.append((
            "gram_mesh_gather", (S, 64, W), f"devices={n_dev} U=16",
            {"a": (S, 64)},
            lambda a: k._gram_mesh_fn(mesh, "shards", True, False, True)(
                jax.device_put(a, spec), idx
            ).sum(axis=0),
            lambda a: k.gram_gather_xla(a, idx),
        ))
    return out, W


def run(production: bool) -> list[dict]:
    """Run every case; one result dict per case (also printed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pilosa_tpu.ops import kernels

    cases, W = _cases(kernels, len(jax.local_devices()), production)
    operands: dict = {}

    def operand(name, dims):
        # ~25% density, made on the device; cached per shape so the
        # 1.34 GiB production stack is generated once
        key = (name, dims)
        if key not in operands:
            if len(operands) > 3:
                operands.clear()
            seed = {"a": 1, "b": 2}[name]
            k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
            shape = dims + (W,)
            operands[key] = jax.block_until_ready(
                jax.random.bits(k1, shape, jnp.uint32)
                & jax.random.bits(k2, shape, jnp.uint32)
            )
        return operands[key]

    results = []
    for name, shape, params, needs, pallas_fn, xla_fn in cases:
        args = [operand(n, d) for n, d in needs.items()]
        rec = {"kernel": name, "shape": list(shape), "params": params}
        t0 = time.perf_counter()
        try:
            got = jax.block_until_ready(pallas_fn(*args))
            rec["seconds"] = round(time.perf_counter() - t0, 2)
            want = jax.block_until_ready(xla_fn(*args))
            same = all(
                np.array_equal(np.asarray(g), np.asarray(w))
                for g, w in zip(
                    jax.tree.leaves(got), jax.tree.leaves(want), strict=True
                )
            )
            rec["status"] = "compiled" if same else "MISMATCH"
        except Exception as e:  # the compiler's verdict is the result
            rec["status"] = "REFUSED"
            rec["error"] = f"{type(e).__name__}: {e}"[:2000]
        results.append(rec)
        line = f"census {name:22s} {str(shape):26s} {params:18s} {rec['status']}"
        if "seconds" in rec:
            line += f" {rec['seconds']}s"
        if "error" in rec:
            line += " :: " + rec["error"].replace("\n", " | ")[:600]
        print(line, flush=True)
    return results


def dispatch_round_trip_ms(n: int = 50) -> dict:
    """One host-synchronised launch of a trivial resident program: what
    every dispatch that waits for its result pays on this machine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.zeros((8, 128), jnp.uint32)
    bump = jax.jit(lambda a: a + jnp.uint32(1))
    jax.block_until_ready(bump(x))
    ms = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(bump(x))
        ms.append((time.perf_counter() - t0) * 1e3)
    return {"median": float(np.median(ms)), "min": min(ms), "n": n}


def main() -> int:
    import jax

    from pilosa_tpu import jaxcache
    from pilosa_tpu.ops import kernels

    jaxcache.configure()
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if on_chip and kernels._interpret():
        print("census: _interpret() is true on a TPU backend", file=sys.stderr)
        return 1
    results = run(production=on_chip)
    bad = [r for r in results if r["status"] != "compiled"]
    out = {
        "census": True,
        "ok": on_chip and not bad,
        "rehearsal": not on_chip,
        "cases": len(results),
        "dispatch_round_trip_ms": dispatch_round_trip_ms(),
        "failed": [f"{r['kernel']}{tuple(r['shape'])}" for r in bad],
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
        "results": results,
    }
    print(json.dumps(out))
    if bad:
        return 1
    return 0 if on_chip else 3


if __name__ == "__main__":
    sys.exit(main())
