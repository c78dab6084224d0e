"""CI smoke check for tiered fragment residency (docs/residency.md).

Boots one real NodeServer with a deliberately tiny ``device_budget``
(room for ~3 field stacks against a 12-field index — 4x
HBM-oversubscribed), drives a concurrent zipfian query burst over
actual HTTP, and asserts the working-set manager engaged end to end:

* the budget **evicted** under pressure and byte accounting stayed
  under cap;
* queries still answered correctly while stacks churned;
* the flight-driven prefetcher **issued** predictive stagings, and a
  prefetch-built stack scored a query **hit** (the useful half of
  ``useful/issued``);
* the operator surfaces carry it: ``pilosa_device_*`` gauges in
  ``/metrics``, the ``residency`` + ``deviceBudget`` blocks in
  ``/debug/vars``, per-fragment tier/pin/heat in ``/debug/fragments``,
  and a ``residency.prefetch`` span under ``?profile=true``.

Exit status 0 on success; any assertion/exception fails the CI step.
Run as ``python -m tools.smoke_residency``.
"""

from __future__ import annotations

import json
import random
import sys
import threading
import urllib.request

N_FIELDS = 12
BUDGET_STACKS = 3  # 12 fields / 3 resident stacks = 4x oversubscribed
BURST_THREADS = 6
QUERIES_PER_THREAD = 30


def _get(uri: str) -> bytes:
    return urllib.request.urlopen(uri, timeout=10).read()


def _post(uri: str, body: bytes, ctype: str = "text/plain") -> bytes:
    req = urllib.request.Request(
        uri, data=body, headers={"Content-Type": ctype}, method="POST"
    )
    return urllib.request.urlopen(req, timeout=10).read()


def main() -> int:
    import jax

    from pilosa_tpu.shardwidth import SHARD_WORDS

    # one field stack as the executor sizes it: [shards, rows, words]
    # uint32, the shard axis padded up to the mesh's device count
    n_dev = jax.local_device_count()
    stack_bytes = n_dev * 2 * SHARD_WORDS * 4

    from pilosa_tpu.server.node import NodeServer

    node = NodeServer(
        port=0,
        device_budget=BUDGET_STACKS * stack_bytes + 256,
        batch_window=0.003,
        batch_max_size=32,
        # rescache off: this smoke asserts device hit/miss and prefetch
        # usefulness on repeat queries, which the semantic result cache
        # would serve before they reach the residency tier
        rescache_entries=0,
    )
    node.start()
    try:
        base = node.uri
        _post(f"{base}/index/ri", b"{}", "application/json")
        width = SHARD_WORDS * 32
        rng = random.Random(7)
        for fi in range(N_FIELDS):
            _post(
                f"{base}/index/ri/field/f{fi}",
                b'{"options": {}}',
                "application/json",
            )
            writes = "".join(
                f"Set({rng.randrange(width)}, f{fi}={row})"
                for row in (1, 2)
                for _ in range(24)
            )
            _post(f"{base}/index/ri/query", writes.encode())

        # concurrent zipfian burst: a hot head that should stay resident
        # (and graduate to a pin) over a cold tail that churns the cap
        errors: list[BaseException] = []

        def worker(seed: int) -> None:
            r = random.Random(seed)
            try:
                for _ in range(QUERIES_PER_THREAD):
                    fi = r.choice((0, 0, 0, 1, 1, r.randrange(N_FIELDS)))
                    resp = json.loads(
                        _post(
                            f"{base}/index/ri/query",
                            f"Count(Intersect(Row(f{fi}=1), Row(f{fi}=2)))".encode(),
                        )
                    )
                    assert "results" in resp, resp
            except BaseException as e:  # surfaced after join
                errors.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True)
            for i in range(BURST_THREADS)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "burst thread hung"
        assert not errors, errors[:3]
        assert node.api.ingest.uploader.flush(10.0), "uploader never idled"

        dbg = json.loads(_get(f"{base}/debug/vars"))
        budget = dbg["device"]
        res = dbg["residency"]
        assert budget["capBytes"] == BUDGET_STACKS * stack_bytes + 256
        assert budget["evictions"] > 0, budget
        assert budget["usedBytes"] <= budget["capBytes"] + stack_bytes, budget
        assert res["prefetchIssued"] > 0, res
        assert res["deviceHits"] > 0, res

        # prefetch-hit, deterministically: stage one known-cold stack
        # through the prefetcher, wait for the upload to land, then
        # query it — the first query hit on a prefetch-built stack is
        # what prefetchUseful counts
        from pilosa_tpu import pql

        idx = node.api.holder.index("ri")
        shard_list = sorted(idx.available_shards())
        cold = next(
            fi
            for fi in range(N_FIELDS)
            if not node.api.executor.stacks.cached(
                idx.field(f"f{fi}"), shard_list, "standard"
            )
        )
        q = f"Count(Intersect(Row(f{cold}=1), Row(f{cold}=2)))"
        import time

        time.sleep(0.06)  # clear the prefetcher's reissue-TTL window
        before = json.loads(_get(f"{base}/debug/vars"))["residency"]
        assert (
            node.api.prefetcher.prefetch_flight([("ri", pql.parse(q), None)])
            == 1
        )
        assert node.api.ingest.uploader.flush(10.0)
        resp = json.loads(_post(f"{base}/index/ri/query?profile=true", q.encode()))
        after = json.loads(_get(f"{base}/debug/vars"))["residency"]
        assert after["prefetchUseful"] > before["prefetchUseful"], (
            before,
            after,
        )

        # ?profile=true carries the residency span when submit-time
        # staging ran for the request (this one found its stack warm, so
        # look for the span on a cold-field query instead)
        cold2 = next(
            fi
            for fi in range(N_FIELDS)
            if not node.api.executor.stacks.cached(
                idx.field(f"f{fi}"), shard_list, "standard"
            )
        )
        prof_resp = json.loads(
            _post(
                f"{base}/index/ri/query?profile=true",
                f"Count(Intersect(Row(f{cold2}=1), Row(f{cold2}=2)))".encode(),
            )
        )
        names = json.dumps(prof_resp.get("profile", {}))
        assert "residency.prefetch" in names, names[:600]

        metrics = _get(f"{base}/metrics").decode()
        for series in (
            "pilosa_device_hits",
            "pilosa_device_misses",
            "pilosa_device_prefetch_issued",
            "pilosa_device_prefetch_useful",
            "pilosa_device_pins",
            "pilosa_device_evictions",
        ):
            assert series in metrics, f"{series} missing from /metrics"

        frags = json.loads(_get(f"{base}/debug/fragments"))
        rows = frags["fragments"]
        assert rows, frags
        for row in rows:
            assert row["residency"] in ("host", "staging", "device", "pinned")
            assert "heat" in row and "pinned" in row, row

        print(
            "smoke_residency OK: "
            f"evictions={budget['evictions']} "
            f"hits={res['deviceHits']} misses={res['deviceMisses']} "
            f"prefetchIssued={after['prefetchIssued']} "
            f"prefetchUseful={after['prefetchUseful']}"
        )
        return 0
    finally:
        node.stop()


if __name__ == "__main__":
    sys.exit(main())
