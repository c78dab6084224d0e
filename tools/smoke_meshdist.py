"""CI smoke check for cluster-on-mesh dispatch.

Boots an in-mesh 3-node ``InProcessCluster`` (every member registers its
holder in the process placement map), runs a distributed Count from a
node with remote-owned shards, and asserts the collective path end to
end over actual HTTP:

* the query answers correctly with ZERO ``client.query_node``
  subrequests — the fan-out was one jit-sharded launch;
* ``/metrics`` shows ``pilosa_dist_mesh_local_total`` advanced;
* ``/debug/vars`` carries a ``dist`` block (placement map + partition
  decisions) with no mesh fallback booked;
* the ``?profile=true`` span tree contains a ``meshDispatch`` span and
  NO ``dist.fanout``/``dist.httpFanout`` leg, and the request itself is
  tail-kept in ``/debug/traces``;
* flipping the ``PILOSA_MESH_DISPATCH=0`` kill switch demotes the same
  cluster to the HTTP relay
  (``pilosa_dist_http_fanout_total{reason="disabled"}`` advances and
  real subrequests flow again).

Exit status 0 on success; any assertion/exception fails the CI step.
Run as ``python -m tools.smoke_meshdist``.
"""

from __future__ import annotations

import json
import os
import sys
import urllib.request

# a real multi-device serving mesh (must land before jax is imported)
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()


def _get(uri: str) -> bytes:
    return urllib.request.urlopen(uri, timeout=10).read()


def _post(uri: str, body: bytes, ctype: str = "text/plain") -> bytes:
    req = urllib.request.Request(
        uri, data=body, headers={"Content-Type": ctype}, method="POST"
    )
    return urllib.request.urlopen(req, timeout=10).read()


def main() -> int:
    from pilosa_tpu.shardwidth import SHARD_WIDTH
    from pilosa_tpu.testing import InProcessCluster

    calls: list[tuple] = []
    # trace_baseline_n=1 keeps every request's trace so the span
    # inspection below never races tail-sampling
    with InProcessCluster(3, replica_n=1, trace_baseline_n=1) as c:
        c.create_index("smk")
        c.create_field("smk", "f")
        c.import_bits("smk", "f", [(0, s * SHARD_WIDTH + 1) for s in range(9)])
        # querier must have at least one remote-owned shard, or the
        # "distributed" Count would be trivially local
        qi = next(
            i
            for i in range(len(c.nodes))
            if any(c.owner_of("smk", s) is not c.nodes[i] for s in range(9))
        )
        base = c.nodes[qi].uri
        for n in c.nodes:
            orig = n.client.query_node

            def wrap(*a, _o=orig, **k):
                calls.append(a)
                return _o(*a, **k)

            n.client.query_node = wrap

        # over real HTTP so the request rides the traced serving plane
        out = json.loads(
            _post(f"{base}/index/smk/query?profile=true", b"Count(Row(f=0))")
        )
        assert out["results"] == [9], out
        assert calls == [], f"mesh dispatch issued HTTP subrequests: {calls!r}"

        metrics = _get(f"{base}/metrics").decode()
        line = next(
            (
                ln
                for ln in metrics.splitlines()
                if ln.startswith("pilosa_dist_mesh_local_total")
            ),
            None,
        )
        assert line, "no pilosa_dist_mesh_local_total in /metrics"
        assert float(line.split()[-1]) >= 1, line

        vars_ = json.loads(_get(f"{base}/debug/vars"))
        dist = vars_.get("dist")
        assert dist, "no dist block in /debug/vars"
        assert dist["meshEnabled"] is True, dist
        assert dist["placement"], dist
        assert dist["meshDispatches"] >= 1, dist
        assert dist["meshFallbacks"] == 0, dist
        assert not any(
            k.startswith("dist_mesh_fallback_total")
            for k in vars_.get("counters", {})
        ), vars_.get("counters")
        assert dist["recentPartitions"], dist

        # span attribution: the dispatch shows up as ONE meshDispatch
        # span with no HTTP fan-out leg anywhere in the tree
        def _span_names(node, out_names):
            out_names.add(node.get("name"))
            for ch in node.get("children", []):
                _span_names(ch, out_names)
            for sp in node.get("subprofiles", []):
                if sp.get("profile"):
                    _span_names(sp["profile"]["tree"], out_names)
            return out_names

        names = _span_names(out["profile"]["tree"], set())
        assert "meshDispatch" in names, names
        assert "dist.fanout" not in names, names
        assert "dist.httpFanout" not in names, names

        # and the request itself was tail-kept in the trace store
        kept = json.loads(_get(f"{base}/debug/traces"))["traces"]
        assert any(
            "http.query"
            in {
                s["name"]
                for s in json.loads(
                    _get(f"{base}/debug/traces?id={t['traceId']}")
                )["spans"]
            }
            for t in kept
        ), "query request not kept in /debug/traces"

        # kill switch: the SAME cluster demotes to the HTTP relay
        os.environ["PILOSA_MESH_DISPATCH"] = "0"
        try:
            out = json.loads(
                _post(f"{base}/index/smk/query", b"Count(Row(f=0))")
            )
            assert out["results"] == [9], out
            assert calls, "kill switch did not force the HTTP fan-out"
            metrics = _get(f"{base}/metrics").decode()
            assert (
                'pilosa_dist_http_fanout_total{reason="disabled"}' in metrics
            ), metrics[:600]
        finally:
            del os.environ["PILOSA_MESH_DISPATCH"]
    import jax

    d = jax.devices()
    print(
        f"meshdist smoke OK on {len(d)} x {d[0].platform} ({d[0].device_kind}): "
        f"zero HTTP subrequests, meshFallbacks 0"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
