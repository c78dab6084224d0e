"""A rescache hit reuses its answer (exec/rescache.py ``Served``,
server/api.py ``query_encoded``): the hit leaves the cache's lock before
anything walks the answer, hands the cached object out uncopied where no
caller can write to it, and the listener sends the bytes the entry's first
hit was sent as.  What has to hold: the bytes are today's, byte for byte;
whatever replaces or removes a result takes its bytes along; whoever may
write gets a copy; nobody writes to a served object; and the cache's own
behaviour (counters, promotion, LRU order) is the parent's, number for
number."""

import hashlib
import json
import random
import sys
import threading
import urllib.request

import pytest

from pilosa_tpu import pql
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec import rescache
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.exec.result import (
    FieldRow,
    GroupCount,
    Pair,
    Row,
    RowIdentifiers,
    ValCount,
    result_to_json,
)
from pilosa_tpu.server.api import API, encode_json
from pilosa_tpu.server.node import NodeServer

SETUP = (
    "Set(1, f=1) Set(2, f=1) Set(3, f=2) Set(9, f=3) "
    "Set(1, g=1) Set(2, g=2) Set(3, g=2) "
    "Set(1, v=10) Set(2, v=32) Set(3, v=7)"
)

# one query a result type of the QueryResult union, and a request of several
RESULT_TYPES = {
    "row": "Row(f=1)",
    "pairs": "TopN(f)",
    "pair": "MaxRow(field=f)",
    "valcount": "Sum(field=v)",
    "rowidentifiers": "Rows(f)",
    "groupcounts": "GroupBy(Rows(f), Rows(g))",
    "int": "Count(Row(f=1))",
    "several": "Count(Row(f=1)) TopN(f) GroupBy(Rows(f), Rows(g))",
}


def _schema(api_or_idx, keys=False):
    idx = api_or_idx
    idx.create_field("f", FieldOptions(keys=keys))
    idx.create_field("g")
    idx.create_field("v", FieldOptions(field_type="int", min_=0, max_=1000))


@pytest.fixture()
def api():
    a = API(batch_window=0.001)
    a.create_index("i")
    _schema(a.holder.index("i"))
    a.query("i", SETUP)
    yield a
    a.close()


def _todays_bytes(holder, index, query) -> bytes:
    """The response as the path without a cache makes it."""
    plain = Executor(holder, rescache_entries=0)
    return encode_json({"results": result_to_json(plain.execute(index, query))})


# -- the bytes ----------------------------------------------------------------


@pytest.mark.parametrize("kind", sorted(RESULT_TYPES))
def test_memoised_bytes_are_todays_bytes(api, kind):
    query = RESULT_TYPES[kind]
    want = _todays_bytes(api.holder, "i", query)
    rc = api.executor.rescache
    sent = []
    for _ in range(4):  # the miss, the hit that encodes, two hits that reuse
        resp = api.query_encoded("i", query)
        sent.append(resp if isinstance(resp, bytes) else encode_json(resp))
        assert encode_json(api.query("i", query)) == want
    assert sent == [want] * 4
    snap = rc.snapshot()
    if kind == "several":
        # a request of several calls takes the copying path, whole
        assert snap["uncopiedHits"] == snap["encodedHits"] == 0
    else:
        # 7 hits of one entry: query()'s first finds no bytes and, returning
        # a dict, leaves none; query_encoded()'s first leaves them; the five
        # after it find them (query()'s too, which encodes its dict anyway)
        assert snap["uncopiedHits"] == 7 and snap["encodedHits"] == 5


def test_bytes_on_the_wire_are_an_uncached_servers(tmp_path):
    """Over HTTP, class by class: a server with the cache against one
    without, same data, the raw body of four sends each."""
    nodes = [
        NodeServer(data_dir=str(tmp_path / name), host="127.0.0.1", port=0,
                   rescache_entries=n)
        for name, n in (("cached", 512), ("plain", 0))
    ]
    try:
        for n in nodes:
            n.start()
            n.api.create_index("i")
            _schema(n.api.holder.index("i"))
            n.api.query("i", SETUP)

        def post(node, query):
            req = urllib.request.Request(
                f"{node.uri}/index/i/query", data=query.encode(), method="POST"
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return r.read()

        for kind, query in sorted(RESULT_TYPES.items()):
            want = post(nodes[1], query)
            assert json.loads(want)["results"], kind
            assert [post(nodes[0], query) for _ in range(4)] == [want] * 4, kind
        snap = nodes[0].api.executor.rescache.snapshot()
        assert snap["encodedHits"] == 2 * (len(RESULT_TYPES) - 1)
        assert nodes[1].api.executor.rescache.snapshot()["hits"] == 0
    finally:
        for n in nodes:
            n.stop()


# -- the memo goes with the result ----------------------------------------------


def _entry(api, query):
    """What the cache says of the query's entry (``ResultCache.entries``)."""
    call = rescache.canonical_str(pql.parse(query).calls[0])
    found = [e for e in api.executor.rescache.entries() if e["call"] == call]
    return found[0] if found else None


def _cached(api, query):
    """The cached object itself: what a keyless one-call probe is served."""
    hit = api.executor.rescache_probe("i", pql.parse(query))
    assert isinstance(hit, rescache.Served)
    return hit[0]


def _warm(api, query):
    """Miss, encoding hit, reusing hit: the entry holds bytes."""
    for _ in range(3):
        api.query_encoded("i", query)
    assert _entry(api, query)["encodedBytes"]


def test_invalidation_takes_the_bytes(api):
    _warm(api, "TopN(g)")
    _warm(api, "Count(Row(g=2))")
    api.query("i", "Set(7, g=2)")
    # the plain entry went, its bytes with it; the next answers are fresh
    assert _entry(api, "Count(Row(g=2))") is None
    for query in ("Count(Row(g=2))", "TopN(g)"):
        resp = api.query_encoded("i", query)
        want = _todays_bytes(api.holder, "i", query)
        assert (resp if isinstance(resp, bytes) else encode_json(resp)) == want


def test_refresh_of_a_maintained_entry_takes_the_bytes(api):
    rc = api.executor.rescache
    _warm(api, "TopN(f)")
    api.query_encoded("i", "TopN(f)")  # third hit: promoted
    e = _entry(api, "TopN(f)")
    assert e["maintained"] and e["encodedBytes"]
    old_result = _cached(api, "TopN(f)")
    api.query("i", "Set(20, f=3)")  # a maintained entry survives the write
    assert _entry(api, "TopN(f)")["encodedBytes"]
    want = _todays_bytes(api.holder, "i", "TopN(f)")
    before = rc.snapshot()
    got = api.query_encoded("i", "TopN(f)")  # refreshes in place
    snap = rc.snapshot()
    assert snap["maintainedHits"] == before["maintainedHits"] + 1
    assert snap["encodedHits"] == before["encodedHits"]  # no bytes to find
    assert got == want  # built anew from the fresh result, and left with it
    assert _entry(api, "TopN(f)")["encodedBytes"] == len(want)
    assert _cached(api, "TopN(f)") is not old_result
    assert api.query_encoded("i", "TopN(f)") == want


def test_demotion_takes_the_bytes():
    a = API(batch_window=0.001, rescache_demote_deltas=2)
    try:
        a.create_index("i")
        _schema(a.holder.index("i"))
        a.query("i", SETUP)
        _warm(a, "TopN(f)")
        a.query_encoded("i", "TopN(f)")
        assert _entry(a, "TopN(f)")["maintained"]
        for col in (30, 31, 32, 33):
            a.query("i", f"Set({col}, f=2)")
        want = _todays_bytes(a.holder, "i", "TopN(f)")
        resp = a.query_encoded("i", "TopN(f)")  # drift past the threshold: demoted, dropped
        assert a.executor.rescache.snapshot()["demotions"] == 1
        assert (resp if isinstance(resp, bytes) else encode_json(resp)) == want
        assert _entry(a, "TopN(f)")["encodedBytes"] is None
    finally:
        a.close()


def test_eviction_takes_the_bytes():
    a = API(batch_window=0.001, rescache_entries=2)
    try:
        a.create_index("i")
        _schema(a.holder.index("i"))
        a.query("i", SETUP)
        _warm(a, "TopN(f)")
        held = a.executor.rescache_probe("i", pql.parse("TopN(f)"))
        a.query_encoded("i", "Count(Row(f=1))")
        a.query_encoded("i", "Count(Row(f=2))")  # two newer entries: TopN(f) is out
        assert _entry(a, "TopN(f)") is None
        assert a.executor.rescache.snapshot()["evictions"] >= 1
        # a holder of the evicted answer may still read it, and leaves bytes nowhere
        assert encode_json({"results": result_to_json(held)}) == held.body
        held.remember(b"late")
        assert _entry(a, "TopN(f)") is None
        a.query_encoded("i", "TopN(f)")
        assert _entry(a, "TopN(f)")["encodedBytes"] is None
    finally:
        a.close()


def test_bytes_are_never_installed_against_a_replaced_result(api):
    """A handler that hit the old result and encodes it while a refresh
    replaces it must not leave the old bytes on the new result."""
    for _ in range(4):
        api.query("i", "TopN(f)")  # the dict form: promoted, no bytes yet
    e = _entry(api, "TopN(f)")
    assert e["maintained"] and e["encodedBytes"] is None
    slow = api.executor.rescache_probe("i", pql.parse("TopN(f)"))
    assert isinstance(slow, rescache.Served) and slow.body is None
    api.query("i", "Set(40, f=3)")
    api.query("i", "TopN(f)")  # the refresh replaces the result
    fast = api.executor.rescache_probe("i", pql.parse("TopN(f)"))
    assert fast[0] is not slow[0] and fast.body is None
    slow.remember(b"bytes of the old result")
    assert _entry(api, "TopN(f)")["encodedBytes"] is None
    fast.remember(b"current")
    assert api.executor.rescache_probe("i", pql.parse("TopN(f)")).body == b"current"


def test_result_and_bytes_are_one_tuple():
    e = rescache._Entry((), [Pair(1, None, 1)], None, None, None)
    assert e.held == ([Pair(1, None, 1)], None)
    e.held = (e.result, b"body")
    e.result = [Pair(2, None, 2)]  # what a refresh does
    assert e.held == ([Pair(2, None, 2)], None)


# -- who gets the cached object, and who a copy -------------------------------------


def test_keyless_single_call_is_served_uncopied(api):
    api.query("i", "TopN(g)")
    assert _cached(api, "TopN(g)") is _cached(api, "TopN(g)")
    assert api.batcher.submit("i", pql.parse("TopN(g)"))[0] is _cached(api, "TopN(g)")
    assert api.executor.rescache.snapshot()["uncopiedHits"] == 4


@pytest.mark.parametrize("case", ["keyed_field", "keyed_index", "several",
                                  "profile", "degraded", "remote", "probe_raw",
                                  "lookup"])
def test_whoever_may_write_gets_a_copy(case):
    a = API(batch_window=0.001)
    try:
        a.create_index("i", {"keys": case == "keyed_index"})
        idx = a.holder.index("i")
        _schema(idx, keys=case == "keyed_field")
        if case == "keyed_index":
            a.query("i", 'Set("a", g=1) Set("b", g=2)')
            query = "Row(g=1)"
        elif case == "keyed_field":
            a.query("i", 'Set(1, f="x") Set(2, f="x") Set(3, f="y")')
            query = "TopN(f)"
        else:
            a.query("i", SETUP)
            query = "TopN(g)"
        ex, rc = a.executor, a.executor.rescache
        first = a.query("i", query)
        q = pql.parse(query + (" Count(Row(g=1))" if case == "several" else ""))
        if case == "several":
            a.query("i", "Count(Row(g=1))")
        call = pql.parse(query).calls[0].clone()
        ex._translate_call(idx, call)
        if case == "profile":
            got = a.query("i", query, profile=True)
            assert got["results"] == first["results"] and "profile" in got
            out = None
        elif case == "degraded":
            out = ex.rescache_degraded("i", q)[0]
        elif case == "remote":
            out = ex.cached_execute_call(idx, call, None)
        elif case == "probe_raw":
            rc.store_raw(("partial", 1), ("v",), ex.execute("i", query)[0])
            out = rc.probe_raw(("partial", 1), ("v",))
        elif case == "lookup":
            out = rc.lookup(idx, call, None)[0]
        else:
            hit = ex.rescache_probe("i", q)
            assert type(hit) is list
            out = hit[0]
        snap = rc.snapshot()
        assert snap["hits"] + snap["degradedHits"] >= 1
        assert snap["uncopiedHits"] == snap["encodedHits"] == 0
        if out is not None:
            assert result_to_json(out) == first["results"][0]
            # writing to what they got reaches no later answer
            for o in out if isinstance(out, list) else [out]:
                o.key = o.keys = o.count = "scribbled"
        if case in ("keyed_field", "keyed_index", "profile"):
            for _ in range(3):  # and never the stored bytes
                resp = a.query_encoded("i", query, profile=case == "profile")
                assert isinstance(resp, dict) and resp["results"] == first["results"]
        else:
            assert a.query("i", query) == first
        assert rc.snapshot()["encodedHits"] == 0
    finally:
        a.close()


# -- the contract: nobody writes to a served object ---------------------------------


class _Tripwire(AssertionError):
    pass


def _refuse(self, *a, **k):
    raise _Tripwire(f"a caller wrote to a served {type(self).__mro__[1].__name__}")


def _frozen(cls):
    return type("Frozen" + cls.__name__, (cls,),
                {"__setattr__": _refuse, "__delattr__": _refuse})


class _FrozenList(list):
    __setitem__ = __delitem__ = __iadd__ = __imul__ = _refuse
    append = extend = insert = pop = remove = clear = sort = reverse = _refuse


class _FrozenDict(dict):
    __setitem__ = __delitem__ = __ior__ = _refuse
    update = pop = popitem = clear = setdefault = _refuse


_FROZEN = {cls: _frozen(cls) for cls in
           (Row, Pair, ValCount, RowIdentifiers, GroupCount, FieldRow)}


def _freeze(obj):
    """The same answer with every write to it an error."""
    if isinstance(obj, list):
        return _FrozenList(_freeze(o) for o in obj)
    if isinstance(obj, dict):
        return _FrozenDict(obj)
    if type(obj) in _FROZEN:
        for name, value in list(vars(obj).items()):
            if isinstance(value, (list, dict)):
                object.__setattr__(obj, name, _freeze(value))
        obj.__class__ = _FROZEN[type(obj)]
    return obj


def test_the_tripwire_trips():
    for obj in (Pair(1, None, 2), ValCount(1, 2), RowIdentifiers([1]),
                GroupCount([FieldRow("f", 1)], 3), Row({}, 4)):
        _freeze(obj)
        with pytest.raises(_Tripwire):
            obj.key = "k"
    gc = _freeze([GroupCount([FieldRow("f", 1)], 3)])
    with pytest.raises(_Tripwire):
        gc[0].group[0].row_key = "k"
    with pytest.raises(_Tripwire):
        gc[0].group.append(None)
    with pytest.raises(_Tripwire):
        gc.append(None)
    with pytest.raises(_Tripwire):
        _freeze(Row({}, 4)).attrs["x"] = 1


@pytest.mark.parametrize("kind", sorted(set(RESULT_TYPES) - {"several"}))
def test_no_caller_writes_to_a_served_object(api, kind):
    """Every path a Served list travels (the batcher's probe, both forms of
    the api's query, the listener's encode) with the cached answer frozen."""
    query = RESULT_TYPES[kind]
    want = _todays_bytes(api.holder, "i", query)
    idx = api.holder.index("i")
    call = pql.parse(query).calls[0].clone()
    api.executor._translate_call(idx, call)
    rc = api.executor.rescache
    frozen = _freeze(Executor(api.holder, rescache_entries=0).execute("i", query)[0])
    _miss, token = rc.lookup(idx, call, None)
    rc.store(token, frozen)
    for _ in range(3):
        assert encode_json(api.query("i", query)) == want
        assert api.query_encoded("i", query) == want
    hit = api.batcher.submit("i", pql.parse(query))
    assert isinstance(hit, rescache.Served) and hit[0] is frozen and hit.body == want
    assert rc.snapshot()["uncopiedHits"] == 7
    # the paths that may write never see it
    copy = api.executor.cached_execute_call(idx, call, None)
    assert result_to_json(copy) == json.loads(want)["results"][0]
    if not isinstance(copy, int):
        assert copy is not frozen


# -- the cache behaves as the parent's ------------------------------------------


REPLAY_POOL = (  # hottest first: the views that promote, then the scalars
    ["TopN(a)", "GroupBy(Rows(a), Rows(b))", "TopN(b)", "GroupBy(Rows(b), Rows(c))"]
    + [f"Count(Row(a={r}))" for r in range(8)]
    + ["TopN(c)", "TopN(a, n=2)", "TopN(b, Row(a=1))", "Rows(a)", "Row(b=1)"]
    + [f"Count(Intersect(Row(a={r}), Row(b={r % 3})))" for r in range(8)]
    + [f"Count(Union(Row(b={r}), Row(c={r % 2})))" for r in range(4)]
    + [f"Sum(Row(a={r}), field=v)" for r in range(4)]
)


def lru_order(cache) -> list:
    """(call, hits, maintained, delta) an entry, least recently used first."""
    return [(e["call"], e["hits"], e["maintained"], e["deltaAccum"]) for e in cache.entries()]


def replay(lookups: int = 2000, seed: int = 35) -> dict:
    """A seeded sequence of ``lookups`` reads as the served path makes them
    (the batcher's probe; on a miss the flight's execute, which looks up
    again and stores), writes in between, against a cache of 16 entries so
    that it turns over.  Uses nothing the parent's tree lacks."""
    rng = random.Random(seed)
    h = Holder()
    h.create_index("i")
    idx = h.index("i")
    for name in "abc":
        idx.create_field(name)
    idx.create_field("v", FieldOptions(field_type="int", min_=0, max_=1000))
    ex = Executor(h, rescache_entries=16, rescache_promote_hits=3,
                  rescache_demote_deltas=4)
    ex.execute("i", " ".join(
        f"Set({c}, a={c % 8}) Set({c}, b={c % 3}) Set({c}, c={c % 2}) Set({c}, v={c * 7 % 1000})"
        for c in range(48)))
    weights = [1.0 / (k + 1) for k in range(len(REPLAY_POOL))]
    answers = hashlib.sha256()
    done = 0
    while done < lookups:
        if rng.random() < 0.04:
            field = rng.choice("abc")
            ex.execute("i", f"Set({rng.randrange(48, 4000)}, {field}={rng.randrange(3)})")
            continue
        query = rng.choices(REPLAY_POOL, weights)[0]
        res = ex.rescache_probe("i", pql.parse(query))
        if res is None:
            res = ex.execute("i", query)
        answers.update(json.dumps(result_to_json(list(res))).encode())
        done += 1
    snap = ex.rescache.snapshot()
    order = hashlib.sha256(repr(lru_order(ex.rescache)).encode())
    return {
        "hits": snap["hits"], "misses": snap["misses"], "stores": snap["stores"],
        "invalidations": snap["invalidations"], "promotions": snap["promotions"],
        "demotions": snap["demotions"], "maintainedHits": snap["maintainedHits"],
        "evictions": snap["evictions"], "entries": snap["entries"],
        "lru": order.hexdigest()[:16], "answers": answers.hexdigest()[:16],
    }


# what the tree before this change gives (commit 15f9688, this function as it
# stands, with ``lru_order`` reading the table itself: it has no ``entries``)
PARENT_REPLAY = {
    "hits": 1387, "misses": 1226, "stores": 613, "invalidations": 364,
    "promotions": 37, "demotions": 17, "maintainedHits": 64, "evictions": 233,
    "entries": 16, "lru": "9d57d92b1413ebfa", "answers": "c63c05628c4b6823",
}


def test_replayed_lookups_count_as_the_parents():
    got = replay()
    assert got["evictions"] > 100 and got["promotions"] > 10 and got["demotions"] > 0
    assert got["maintainedHits"] > 0 and got["invalidations"] > 100
    assert got == PARENT_REPLAY


# -- the lock covers the table, not the answer -----------------------------------


class _CountingLock:
    """The cache's lock, counting the Python and C calls its holder makes
    while it holds it: work done under the lock, whatever the wall clock."""

    def __init__(self):
        self._lock = threading.Lock()
        self.holds = 0
        self.worst = 0
        self._calls = 0

    def _count(self, frame, event, arg):
        if event in ("call", "c_call"):
            self._calls += 1

    def acquire(self, *a):
        got = self._lock.acquire(*a)
        self._calls = 0
        sys.setprofile(self._count)
        return got

    def release(self):
        sys.setprofile(None)
        self.holds += 1
        self.worst = max(self.worst, self._calls)
        self._lock.release()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()


def test_a_large_hit_does_not_hold_the_lock_for_its_copy():
    """One thread hits a 4,000-group answer while 8 others probe scalars:
    no hold of the cache's lock may do work that grows with the answer,
    whichever form the hit takes (uncopied, or the caller's own copy)."""
    h = Holder()
    h.create_index("i")
    idx = h.index("i")
    for name in ("a", "b", "c"):
        idx.create_field(name)
    class Counted(rescache.ResultCache):
        def __init__(self):
            super().__init__()
            self._lock = self.counting = _CountingLock()

    ex = Executor(h)
    ex.rescache = rc = Counted()
    ex.execute("i", "Set(1, a=1) Set(1, b=1) Set(1, c=1)")
    big_q = pql.parse("GroupBy(Rows(a), Rows(b), Rows(c))")
    big_call = big_q.calls[0].clone()
    ex._translate_call(idx, big_call)
    groups = [GroupCount([FieldRow("a", x), FieldRow("b", y), FieldRow("c", z)], 1)
              for x in range(10) for y in range(8) for z in range(50)]
    _res, tok = rc.lookup(idx, big_call, None)
    rc.store(tok, groups)
    scalars = [pql.parse(f"Count(Row(a={r}))") for r in range(8)]
    for q in scalars:
        ex.execute("i", q)
    lock = rc.counting
    lock.holds = lock.worst = 0

    errors = []
    stop = threading.Event()

    def big():
        try:
            for k in range(12):
                if k % 2:
                    got = ex.rescache_probe("i", big_q)
                    assert isinstance(got, rescache.Served) and got[0] is groups
                else:
                    got, _ = rc.lookup(idx, big_call, None)
                    assert got == groups and got is not groups
        except BaseException as e:  # the main thread raises it
            errors.append(e)
        finally:
            stop.set()

    def small(q):
        try:
            while not stop.is_set():
                assert ex.rescache_probe("i", q) is not None
        except BaseException as e:
            errors.append(e)
            stop.set()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=small, args=(q,)) for q in scalars]
        threads.append(threading.Thread(target=big))
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not errors, errors
    assert lock.holds >= 12 + 8
    # a copy of 4,000 groups makes over 16,000 calls; the bookkeeping a dozen
    assert lock.worst < 40, lock.worst
