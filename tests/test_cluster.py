"""Cluster layer tests (reference: cluster_internal_test.go — hasher /
partition / placement matrices; server/cluster_test.go + executor_test.go
MustRunCluster multi-node behavior specs)."""

import numpy as np
import pytest

from pilosa_tpu.cluster import (
    Cluster,
    Node,
    Topology,
    jump_hash,
    partition_hash,
)
from pilosa_tpu.cluster.wire import decode_results, encode_results
from pilosa_tpu.exec.result import GroupCount, FieldRow, Pair, Row, RowIdentifiers, ValCount
from pilosa_tpu.shardwidth import SHARD_WIDTH
from pilosa_tpu.testing import InProcessCluster

import jax.numpy as jnp


# -- hashing ----------------------------------------------------------------


def test_jump_hash_range_and_determinism():
    for n in (1, 2, 3, 7, 64):
        for key in range(50):
            b = jump_hash(key, n)
            assert 0 <= b < n
            assert b == jump_hash(key, n)


def test_jump_hash_minimal_movement():
    """Growing the bucket count must move only ~1/n of keys (the property
    the reference relies on for cheap resize, cluster.go:922-934)."""
    keys = list(range(2000))
    before = [jump_hash(k, 4) for k in keys]
    after = [jump_hash(k, 5) for k in keys]
    moved = sum(1 for b, a in zip(before, after) if b != a)
    assert moved < len(keys) * 0.35  # expect ~20%
    # every moved key lands in the NEW bucket
    assert all(a == 4 for b, a in zip(before, after) if b != a)


def test_jump_hash_balance():
    counts = [0] * 8
    for k in range(8000):
        counts[jump_hash(k, 8)] += 1
    assert min(counts) > 700  # roughly uniform


def test_partition_hash_spreads_shards():
    ps = {partition_hash("i", s, 256) for s in range(200)}
    assert len(ps) > 100
    assert all(0 <= p < 256 for p in ps)
    # index name participates in the hash
    assert [partition_hash("a", s, 256) for s in range(20)] != [
        partition_hash("b", s, 256) for s in range(20)
    ]


# -- placement --------------------------------------------------------------


def _cluster_of(n, replica_n=1):
    c = Cluster("node0", replica_n=replica_n)
    c.set_static([Node(id=f"node{i}", uri=f"http://n{i}") for i in range(n)])
    return c


def test_shard_nodes_replicas_distinct():
    c = _cluster_of(4, replica_n=3)
    for shard in range(50):
        nodes = c.shard_nodes("i", shard)
        assert len(nodes) == 3
        assert len({n.id for n in nodes}) == 3


def test_replica_n_capped_by_node_count():
    c = _cluster_of(2, replica_n=5)
    assert len(c.shard_nodes("i", 0)) == 2


def test_placement_agrees_across_nodes():
    """Every node computes identical placement (pure function of the
    sorted membership)."""
    a = _cluster_of(5, replica_n=2)
    b = Cluster("node3", replica_n=2)
    b.set_static([Node(id=f"node{i}", uri=f"http://n{i}") for i in range(5)])
    for shard in range(64):
        assert [n.id for n in a.shard_nodes("x", shard)] == [
            n.id for n in b.shard_nodes("x", shard)
        ]


def test_shards_by_node_partitions_all_shards():
    c = _cluster_of(3)
    shards = list(range(40))
    groups = c.shards_by_node("i", shards)
    got = sorted(s for g in groups.values() for s in g)
    assert got == shards


def test_cluster_state_machine():
    c = _cluster_of(3, replica_n=2)
    assert c.determine_state() == "NORMAL"
    c.mark_node_state("node1", "DOWN")
    assert c.state == "DEGRADED"
    c.mark_node_state("node2", "DOWN")
    assert c.state == "STARTING"
    c.mark_node_state("node1", "READY")
    c.mark_node_state("node2", "READY")
    assert c.state == "NORMAL"


def test_topology_persistence(tmp_path):
    t = Topology(["b", "a"])
    t.add("c")
    t.save(str(tmp_path))
    t2 = Topology.load(str(tmp_path))
    assert t2.node_ids == ["a", "b", "c"]


# -- wire encoding ----------------------------------------------------------


def test_wire_roundtrip():
    row = Row({2: jnp.asarray(np.array([5, 0, 9], dtype=np.uint32))})
    results = [
        row,
        ValCount(value=7, count=3),
        [Pair(id=1, count=10), Pair(id=2, count=5)],
        RowIdentifiers(rows=[1, 2, 3]),
        [GroupCount(group=[FieldRow(field="f", row_id=4)], count=9)],
        True,
        123,
    ]
    out = decode_results(encode_results(results))
    assert np.array_equal(np.asarray(out[0].segments[2]), [5, 0, 9])
    assert out[1] == ValCount(value=7, count=3)
    assert out[2][0].id == 1 and out[2][1].count == 5
    assert out[3].rows == [1, 2, 3]
    assert out[4][0].group[0].field == "f" and out[4][0].count == 9
    assert out[5] is True and out[6] == 123


# -- in-process multi-node cluster ------------------------------------------


@pytest.fixture(scope="module")
def cluster3():
    with InProcessCluster(3, replica_n=1) as c:
        yield c


def test_schema_broadcast(cluster3):
    cluster3.create_index("ci")
    cluster3.create_field("ci", "f")
    for node in cluster3.nodes:
        assert node.holder.index("ci") is not None
        assert node.holder.field("ci", "f") is not None


def test_distributed_set_and_count(cluster3):
    cluster3.create_index("ci2")
    cluster3.create_field("ci2", "f")
    # columns spanning several shards → bits land on different nodes
    cols = [1, 5, SHARD_WIDTH + 3, 2 * SHARD_WIDTH + 9, 5 * SHARD_WIDTH + 1]
    for col in cols:
        res = cluster3.query(0, "ci2", f"Set({col}, f=1)")
        assert res["results"][0] is True
    # data is actually distributed: no single node holds every shard
    holding = [
        n
        for n in cluster3.nodes
        if n.holder.field("ci2", "f") is not None
        and len(n.holder.field("ci2", "f").view("standard").fragments
                if n.holder.field("ci2", "f").view("standard") else [])
    ]
    # every node answers the same full count
    for i in range(3):
        res = cluster3.query(i, "ci2", "Count(Row(f=1))")
        assert res["results"][0] == len(cols), f"node {i}"
    row = cluster3.query(1, "ci2", "Row(f=1)")["results"][0]
    assert sorted(row["columns"]) == sorted(cols)


def test_data_actually_distributed(cluster3):
    cluster3.create_index("ci3")
    cluster3.create_field("ci3", "f")
    bits = [(0, s * SHARD_WIDTH) for s in range(12)]
    cluster3.import_bits("ci3", "f", bits)
    nodes_with_data = 0
    for n in cluster3.nodes:
        f = n.holder.field("ci3", "f")
        v = f.view("standard") if f else None
        if v is not None and len(v.fragments):
            nodes_with_data += 1
    assert nodes_with_data >= 2  # 12 shards over 3 nodes: not all on one
    assert cluster3.query(2, "ci3", "Count(Row(f=0))")["results"][0] == 12


def test_distributed_topn_and_bsi(cluster3):
    cluster3.create_index("ci4")
    cluster3.create_field("ci4", "f")
    cluster3.create_field(
        "ci4", "v", {"type": "int", "min": 0, "max": 1000}
    )
    # row 1 gets 3 bits, row 2 gets 2, row 3 gets 1 — across shards
    bits = [
        (1, 0), (1, SHARD_WIDTH), (1, 2 * SHARD_WIDTH),
        (2, 1), (2, SHARD_WIDTH + 1),
        (3, 2),
    ]
    cluster3.import_bits("ci4", "f", bits)
    pairs = cluster3.query(0, "ci4", "TopN(f, n=2)")["results"][0]
    assert [(p["id"], p["count"]) for p in pairs] == [(1, 3), (2, 2)]
    # BSI values across shards
    for node_i, (col, val) in enumerate(
        [(0, 100), (SHARD_WIDTH, 250), (2 * SHARD_WIDTH + 7, 650)]
    ):
        cluster3.query(node_i % 3, "ci4", f"Set({col}, v={val})")
    res = cluster3.query(1, "ci4", "Sum(field=v)")["results"][0]
    assert res == {"value": 1000, "count": 3}
    rng = cluster3.query(2, "ci4", "Row(v > 200)")["results"][0]
    assert sorted(rng["columns"]) == [SHARD_WIDTH, 2 * SHARD_WIDTH + 7]


def test_distributed_topn_second_pass_exactness(cluster3):
    """A row that is NOT any single node's #1 but IS the global #1 must
    win: per-node truncation alone would return the wrong row (and
    wrong counts), so this asserts the candidate-union refetch
    (reference executor.go:884-999 second phase).

    Layout: shard A (node X) has row 1 x4 bits, row 9 x3; shard B
    (node Y, a different node) has row 9 x3, row 2 x1.  Phase-1 top-1
    lists are [(1,4)] and [(9,3)] — a naive merge picks row 1 with
    count 4, but the true global top is row 9 with count 6."""
    cluster3.create_index("ci_topn2")
    cluster3.create_field("ci_topn2", "f")
    owner0 = cluster3.owner_of("ci_topn2", 0)
    shard_b = next(
        s
        for s in range(1, 64)
        if cluster3.owner_of("ci_topn2", s) is not owner0
    )
    bits = []
    bits += [(1, c) for c in range(4)]  # shard A: row 1 x4
    bits += [(9, 100 + c) for c in range(3)]  # shard A: row 9 x3
    base = shard_b * SHARD_WIDTH
    bits += [(9, base + c) for c in range(3)]  # shard B: row 9 x3
    bits += [(2, base + 100)]  # shard B: row 2 x1
    cluster3.import_bits("ci_topn2", "f", bits)
    pairs = cluster3.query(0, "ci_topn2", "TopN(f, n=1)")["results"][0]
    assert [(p["id"], p["count"]) for p in pairs] == [(9, 6)]
    pairs = cluster3.query(1, "ci_topn2", "TopN(f, n=2)")["results"][0]
    assert [(p["id"], p["count"]) for p in pairs] == [(9, 6), (1, 4)]
    # every node agrees (any node can coordinate the two-phase query)
    for i in range(3):
        pairs = cluster3.query(i, "ci_topn2", "TopN(f, n=3)")["results"][0]
        assert [(p["id"], p["count"]) for p in pairs] == [
            (9, 6), (1, 4), (2, 1),
        ]


def test_distributed_groupby_and_rows(cluster3):
    cluster3.create_index("ci5")
    cluster3.create_field("ci5", "a")
    cluster3.create_field("ci5", "b")
    bits_a = [(0, 0), (0, SHARD_WIDTH), (1, 2 * SHARD_WIDTH)]
    bits_b = [(5, 0), (5, 2 * SHARD_WIDTH), (6, SHARD_WIDTH)]
    cluster3.import_bits("ci5", "a", bits_a)
    cluster3.import_bits("ci5", "b", bits_b)
    rows = cluster3.query(0, "ci5", "Rows(a)")["results"][0]
    assert rows["rows"] == [0, 1]
    groups = cluster3.query(1, "ci5", "GroupBy(Rows(a), Rows(b))")["results"][0]
    got = {
        tuple(g["rowID"] for g in gc["group"]): gc["count"] for gc in groups
    }
    assert got == {(0, 5): 1, (0, 6): 1, (1, 5): 1}


def test_keyed_index_in_cluster(cluster3):
    cluster3.create_index("ck", {"keys": True})
    cluster3.create_field("ck", "f", {"keys": True})
    # writes through DIFFERENT nodes must allocate consistent ids via the
    # translation primary
    cluster3.query(1, "ck", 'Set("alpha", f="r1")')
    cluster3.query(2, "ck", 'Set("beta", f="r1")')
    cluster3.query(0, "ck", 'Set("gamma", f="r2")')
    for i in range(3):
        res = cluster3.query(i, "ck", 'Row(f="r1")')["results"][0]
        assert sorted(res["keys"]) == ["alpha", "beta"], f"node {i}"
    assert cluster3.query(1, "ck", 'Count(Row(f="r2"))')["results"][0] == 1


def test_translate_log_replication_and_primary_takeover():
    """Replicas stream the primary's key log (reference translate.go:91-97
    + cluster.go:1983-1996): after a sync pass every node serves
    ids->keys locally and holds a full local .keys-feedable copy; when
    the primary dies, reads keep working on replicas, and after
    set-coordinator takeover, NEW key allocation resumes on the new
    primary with no translations lost."""
    with InProcessCluster(3, replica_n=2) as c:
        c.create_index("ck2", {"keys": True})
        c.create_field("ck2", "f", {"keys": True})
        # keyed columns allocate sequential ids -> they all land in
        # shard 0; make the translation primary (= coordinator) the one
        # node NOT replicating shard 0, so writes can survive its death
        replica_ids = {
            n.id for n in c.nodes[0].cluster.shard_nodes("ck2", 0)
        }
        primary = next(n for n in c.nodes if n.node_id not in replica_ids)
        c.nodes[0].api.set_coordinator(primary.node_id)
        c.coordinator_id = primary.node_id
        survivors = [n for n in c.nodes if n is not primary]

        c.query(0, "ck2", 'Set("alpha", f="r1")')
        c.query(1, "ck2", 'Set("beta", f="r1")')
        c.query(2, "ck2", 'Set("gamma", f="r2")')

        # replicate the key log (anti-entropy carrier)
        stats = c.sync_all()
        assert stats["translate_entries"] > 0
        # every survivor's LOCAL store now holds every mapping
        baseline = {}
        for n in survivors:
            local = n.api.executor.translator.local
            got = local.translate_keys(
                "ck2", "", ["alpha", "beta", "gamma"], create=False
            )
            assert all(i != 0 for i in got), (n.node_id, got)
            baseline[n.node_id] = got

        # ---- kill the translation primary -----------------------------
        pi = next(i for i, n in enumerate(c.nodes) if n is primary)
        c.stop_node(pi)

        # ids->keys reads are served from the replicated local copies
        for n in survivors:
            idx_node = next(
                i for i, m in enumerate(c.nodes) if m is n
            )
            res = c.query(idx_node, "ck2", 'Row(f="r1")')["results"][0]
            assert sorted(res["keys"]) == ["alpha", "beta"]

        # ---- takeover: move the primary role to a survivor -------------
        new_primary = survivors[0]
        new_primary.api.set_coordinator(new_primary.node_id)
        for n in survivors:
            assert n.cluster.coordinator_id == new_primary.node_id

        # NEW key allocation resumes (forwarded to the new primary by
        # the other survivor) and loses nothing
        wi = next(i for i, m in enumerate(c.nodes) if m is survivors[1])
        c.query(wi, "ck2", 'Set("delta", f="r1")')
        for n in survivors:
            i = next(j for j, m in enumerate(c.nodes) if m is n)
            res = c.query(i, "ck2", 'Row(f="r1")')["results"][0]
            assert sorted(res["keys"]) == ["alpha", "beta", "delta"]
        # old ids unchanged on the new primary (no reallocation) and the
        # new key got a fresh non-colliding id
        local = new_primary.api.executor.translator.local
        assert (
            local.translate_keys(
                "ck2", "", ["alpha", "beta", "gamma"], create=False
            )
            == baseline[new_primary.node_id]
        )
        ids = local.translate_keys(
            "ck2", "", ["alpha", "beta", "gamma", "delta"], create=False
        )
        assert 0 not in ids and len(set(ids)) == 4


def test_remote_available_shards_propagate(cluster3):
    cluster3.create_index("ci6")
    cluster3.create_field("ci6", "f")
    cluster3.import_bits("ci6", "f", [(0, s * SHARD_WIDTH) for s in range(8)])
    # every node knows the full shard set even though it holds a subset
    for n in cluster3.nodes:
        f = n.holder.field("ci6", "f")
        assert len(f.available_shards()) == 8, n.node_id


def test_replica_failover():
    """Query fan-out retries a dead node's shards on the remaining
    replica (reference executor.go:2495-2506)."""
    with InProcessCluster(3, replica_n=2) as c:
        c.create_index("fi")
        c.create_field("fi", "f")
        bits = [(0, s * SHARD_WIDTH + 1) for s in range(10)]
        c.import_bits("fi", "f", bits)
        assert c.query(0, "fi", "Count(Row(f=0))")["results"][0] == 10
        # kill a non-coordinator node
        victim = 1 if c.nodes[1].node_id != c.coordinator_id else 2
        coord = next(i for i, n in enumerate(c.nodes) if n.node_id == c.coordinator_id)
        c.stop_node(victim)
        assert c.query(coord, "fi", "Count(Row(f=0))")["results"][0] == 10


def test_import_roaring_replicated():
    from pilosa_tpu.storage import roaring

    with InProcessCluster(2, replica_n=2) as c:
        c.create_index("ri")
        c.create_field("ri", "f")
        positions = np.array([0, 1, 100], dtype=np.uint64)
        data = roaring.serialize(positions)
        c.nodes[0].api.import_roaring("ri", "f", 0, data)
        # replica_n=2 on 2 nodes → both hold the fragment
        for n in c.nodes:
            frag = n.holder.fragment("ri", "f", "standard", 0)
            assert frag is not None and frag.total_count() == 3
        assert c.query(1, "ri", "Count(Row(f=0))")["results"][0] == 3


import contextlib


@contextlib.contextmanager
def _delayed_client(dist, delay):
    """Patch dist.client.query_node to sleep ``delay`` per call and count
    concurrent in-flight calls; yields a dict with max_inflight."""
    import threading
    import time

    stats = {"max_inflight": 0}
    inflight = 0
    lock = threading.Lock()
    orig = dist.client.query_node

    def slow_query_node(*args, **kwargs):
        nonlocal inflight
        with lock:
            inflight += 1
            stats["max_inflight"] = max(stats["max_inflight"], inflight)
        try:
            time.sleep(delay)
            return orig(*args, **kwargs)
        finally:
            with lock:
                inflight -= 1

    dist.client.query_node = slow_query_node
    try:
        yield stats
    finally:
        dist.client.query_node = orig


def test_parallel_node_fanout():
    """Remote nodes are queried concurrently, not serially: with an
    injected per-remote-call delay, the calls to two remote nodes are in
    flight at once (reference goroutine-per-node mapper,
    executor.go:2520-2573).  Overlap is counted, not timed: a wall-clock
    bound fails on a loaded machine with nothing wrong."""
    # mesh_dispatch=False: this test measures HTTP fan-out concurrency;
    # mesh-local dispatch would answer without any remote calls to overlap
    with InProcessCluster(3, replica_n=1, mesh_dispatch=False) as c:
        c.create_index("pf")
        c.create_field("pf", "f")
        # enough shards that every node owns some
        bits = [(0, s * SHARD_WIDTH + 1) for s in range(12)]
        c.import_bits("pf", "f", bits)
        coord = next(
            i for i, n in enumerate(c.nodes) if n.node_id == c.coordinator_id
        )
        dist = c.nodes[coord].api.dist
        assert dist is not None
        with _delayed_client(dist, 0.75) as stats:
            res = c.query(coord, "pf", "Count(Row(f=0))")
        assert res["results"][0] == 12
        assert stats["max_inflight"] >= 2, "remote queries never overlapped"


def test_parallel_replica_write_fanout():
    """Point writes hit every replica concurrently (reference
    executor.go:2140-2207 fans replica writes): the two remote replicas'
    writes are in flight at once, and every replica holds the bit."""
    with InProcessCluster(3, replica_n=3) as c:
        c.create_index("pw")
        c.create_field("pw", "f")
        coord = next(
            i for i, n in enumerate(c.nodes) if n.node_id == c.coordinator_id
        )
        dist = c.nodes[coord].api.dist
        with _delayed_client(dist, 0.75) as stats:
            res = c.query(coord, "pw", "Set(3, f=7)")
        assert res["results"][0] is True
        assert stats["max_inflight"] >= 2, "replica writes never overlapped"
        # the write really landed everywhere
        for n in c.nodes:
            frag = n.holder.fragment("pw", "f", "standard", 0)
            assert frag is not None and frag.get_bit(7, 3)


def test_remove_node_and_abort_over_http():
    """Operator endpoints (reference http/handler.go routes
    /cluster/resize/remove-node and /cluster/resize/abort +
    /recalculate-caches): remove a node through the resize protocol via
    HTTP, and clear a stuck RESIZING state with abort."""
    import json as _json
    import urllib.request

    def post(uri, path, body=None):
        req = urllib.request.Request(
            f"{uri}{path}",
            data=_json.dumps(body or {}).encode(),
            method="POST",
        )
        return _json.load(urllib.request.urlopen(req, timeout=10))

    with InProcessCluster(3, replica_n=2) as c:
        c.create_index("rn")
        c.create_field("rn", "f")
        bits = [(1, s * SHARD_WIDTH + 7) for s in range(9)]
        c.import_bits("rn", "f", bits)
        coord = c.coordinator
        # recalculate-caches: accepted no-op
        assert post(coord.uri, "/recalculate-caches") == {}
        victim = next(n for n in c.nodes if n.node_id != coord.node_id)
        out = post(coord.uri, "/cluster/resize/remove-node", {"id": victim.node_id})
        assert out == {"removed": victim.node_id}
        survivors = [n for n in c.nodes if n is not victim]
        for n in survivors:
            assert len(n.cluster.nodes) == 2
            assert n.api.state == "NORMAL"
        # data survived the removal (replica_n=2 covered every shard)
        got = survivors[0].api.query("rn", "Count(Row(f=1))")["results"][0]
        assert got == 9
        victim.stop()
        c.nodes.remove(victim)

        # wedge a node in RESIZING, then abort from the coordinator
        survivors[1].api.receive_message(
            {"type": "cluster-status", "state": "RESIZING"}
        )
        assert survivors[1].api.state == "RESIZING"
        out = post(coord.uri, "/cluster/resize/abort")
        assert out == {"aborted": True}
        for n in survivors:
            assert n.api.state == "NORMAL"
        got = survivors[1].api.query("rn", "Count(Row(f=1))")["results"][0]
        assert got == 9


def test_max_writes_enforced_on_cluster_path(cluster3):
    """The write cap guards the coordinator boundary for clustered
    queries too (reference executor.go:138 runs for every Execute)."""
    from pilosa_tpu.server.api import ApiError

    cluster3.create_index("mw")
    cluster3.create_field("mw", "f")
    for n in cluster3.nodes:
        n.api.executor.max_writes_per_request = 3
    try:
        cluster3.query(1, "mw", "Set(1, f=1) Set(2, f=1) Set(3, f=1)")
        with pytest.raises(ApiError):
            cluster3.query(
                1, "mw", "Set(1, f=1) Set(2, f=1) Set(3, f=1) Set(4, f=1)"
            )
    finally:
        for n in cluster3.nodes:
            n.api.executor.max_writes_per_request = (
                n.api.executor.DEFAULT_MAX_WRITES_PER_REQUEST
            )


def test_anti_entropy_background_loop_converges_translation():
    """The periodic anti-entropy loop (reference server.go:494-546
    monitorAntiEntropy) carries translate-log replication: replicas
    converge WITHOUT any manual sync call."""
    import time

    with InProcessCluster(3, replica_n=2) as c:
        c.create_index("ae", {"keys": True})
        c.create_field("ae", "f", {"keys": True})
        for n in c.nodes:
            n.start_anti_entropy(0.15)
        c.query(0, "ae", 'Set("alpha", f="r1")')
        c.query(1, "ae", 'Set("beta", f="r1")')
        primary_id = c.nodes[0].cluster.translate_primary().id
        replicas = [n for n in c.nodes if n.node_id != primary_id]
        deadline = time.time() + 8
        while time.time() < deadline:
            done = all(
                0
                not in n.api.executor.translator.local.translate_keys(
                    "ae", "", ["alpha", "beta"], create=False
                )
                for n in replicas
            )
            if done:
                break
            time.sleep(0.1)
        assert done, "replicas did not converge via the background loop"
