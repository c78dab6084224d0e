"""The one general traffic generator.

A traffic mix is a data file (``traffic/<mix>.json``).  This module turns
a mix, a configuration and a seed into request streams: one stream per
connection, each a function of ``(seed, phase, connection)`` alone, so the
same seed sends the same requests whatever the timing.

Mix file::

    {"loop": "closed", "connections": 32, "processes": 4,
     "zipf_theta": 0.99, "check_one_in": 40, "check_max": 128,
     "classes": {
       "<class>": {"weight": 40,
                   "variants": ["Count(Intersect(Row(cab_type={c}), Row(passenger_count={p})))"],
                   "slots": {"c": {"pick": "row", "field": "cab_type"}, ...}}}}

Every class reads; each connection sends its next request when the last
has answered (a closed loop).

A mix may write beside its readers, through a ``stream`` block::

     "stream": {"every_s": 4.0}

One connection of its own imports the configuration's free slabs (those of
every shard past the load's ``columns``), one slab every ``every_s``
seconds from the scheduled time, round-robin over the shards
(``Mix.slabs``); a slab's data is ``datagen.gen_slab`` of the seed.  The
warm-up imports into one shard and into two (``run.SWEEP_SHARDS``) and reads
after each (``Mix.every_variant``), so that the program has refreshed its
device copies in the shapes a window meets, and filled a flight with the
misses an emptied result cache brings, before the window.  A mix
without the block sends what it sent before there was one.

Classes are dealt from a shuffled deck that holds each class ``weight``
times, so every seed sends the classes in the same shares, in another
order.  Slot picks:

``row``            a row of ``field``, zipfian by popularity -> ``{x}``
``int``            uniform in the int ``field``'s range       -> ``{x}``
``int_range``      two of those, ordered         -> ``{x[lo]}``, ``{x[hi]}``
``int_band``       a centre uniform in the int ``field``'s range and the band
                   ``around`` it, ``[below, above]``, clipped to the range,
                   both ends in               -> ``{x[lo]}``, ``{x[hi]}``
``row_run``        ``k`` consecutive row ids of ``field`` that all occur, the
                   first drawn like a ``row`` -> ``{x[0]}`` .. ``{x[k-1]}``
``row_under``      a row of ``field`` among those whose ancestor (a field
                   that follows it, ``datagen``) has the row that slot ``of``
                   picked in the same request; two such slots are two
                   cities of one nation, each drawn alone     -> ``{x}``
"""

from __future__ import annotations

import hashlib

import numpy as np

from datagen import ancestor_map, fields_by_name, popularity_order, slabs_per_shard, width_of

PHASES = {"window": 0, "warm": 1}


class Mix:
    def __init__(self, cfg: dict, mix: dict):
        self.cfg = cfg
        self.fields = fields_by_name(cfg)
        self.theta = float(mix.get("zipf_theta", 0.99))
        self.classes = mix["classes"]
        self.deck = [name for name, c in self.classes.items() for _ in range(int(c["weight"]))]
        self._cdf: dict[int, np.ndarray] = {}
        self._order = {n: popularity_order(f, self.fields) for n, f in self.fields.items()
                       if f["kind"] == "set"}
        self._runs: dict[tuple[str, int], np.ndarray] = {}
        self._under: dict[tuple[str, str], dict[int, np.ndarray]] = {}
        self.streams = mix.get("stream")  # None: the mix only reads

    def _zipf(self, rng, n: int) -> int:
        if n not in self._cdf:
            cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -self.theta)
            self._cdf[n] = cdf / cdf[-1]
        return min(int(np.searchsorted(self._cdf[n], rng.random(), side="right")), n - 1)

    def _ranked(self, rng, order: np.ndarray, uniform: bool) -> int:
        """One of ``order`` (row ids, the most common first): any as likely, or zipfian by rank."""
        return int(order[rng.integers(len(order)) if uniform else self._zipf(rng, len(order))])

    def _row(self, rng, field: str, uniform: bool) -> int:
        return self._ranked(rng, self._order[field], uniform)

    def _run_starts(self, field: str, k: int) -> np.ndarray:
        """The rows of ``field`` that start ``k`` consecutive ids which all occur, by popularity."""
        if (field, k) not in self._runs:
            order = self._order[field]
            occurs = np.zeros(int(self.fields[field]["rows"]) + k, bool)
            occurs[order] = True
            starts = order[[bool(occurs[r:r + k].all()) for r in order]]
            if not len(starts):
                raise ValueError(f"{field} has no {k} consecutive rows that all occur")
            self._runs[field, k] = starts
        return self._runs[field, k]

    def _children(self, field: str, ancestor: str) -> dict[int, np.ndarray]:
        """Per row of ``ancestor``, the rows of ``field`` under it that occur, by popularity."""
        if (field, ancestor) not in self._under:
            order = self._order[field]
            up = ancestor_map(self.fields, field, ancestor)[order]
            self._under[field, ancestor] = {int(a): order[up == a] for a in np.unique(up)}
        return self._under[field, ancestor]

    def _pick(self, rng, spec: dict, uniform: bool, slots: dict | None = None, specs: dict | None = None):
        kind = spec["pick"]
        if kind == "row":
            return self._row(rng, spec["field"], uniform)
        if kind == "int_band":
            f = self.fields[spec["field"]]
            centre = int(rng.integers(f["min"], f["max"] + 1))
            below, above = spec["around"]
            return {"lo": max(int(f["min"]), centre - int(below)), "hi": min(int(f["max"]), centre + int(above))}
        if kind == "row_run":
            k = int(spec["k"])
            first = self._ranked(rng, self._run_starts(spec["field"], k), uniform)
            return [first + i for i in range(k)]
        if kind == "row_under":
            above = specs[spec["of"]]
            if above["pick"] != "row":
                raise ValueError(f"row_under of slot {spec['of']!r}, which is no row pick")
            return self._ranked(rng, self._children(spec["field"], above["field"])[slots[spec["of"]]], uniform)
        if kind == "int":
            f = self.fields[spec["field"]]
            return int(rng.integers(f["min"], f["max"] + 1))
        if kind == "int_range":
            f = self.fields[spec["field"]]
            lo, hi = sorted(int(x) for x in rng.integers(f["min"], f["max"] + 1, 2))
            return {"lo": lo, "hi": hi + 1}
        raise ValueError(f"unknown slot pick {kind!r}")

    def _slots(self, rng, specs: dict, uniform: bool) -> dict:
        """A value for every slot of a class, in the file's order; a ``row_under`` after the rows it is drawn under."""
        slots = {name: self._pick(rng, spec, uniform) for name, spec in specs.items()
                 if spec["pick"] != "row_under"}
        for name, spec in specs.items():
            if spec["pick"] == "row_under":
                slots[name] = self._pick(rng, spec, uniform, slots, specs)
        return slots

    def _draw(self, rng, cls: str, variant: int | None, uniform: bool) -> tuple[str, set]:
        """One request of the class and the (field, row) pairs it names."""
        c = self.classes[cls]
        specs = c.get("slots", {})
        slots = self._slots(rng, specs, uniform)
        variants = c["variants"]
        if variant is None:
            variant = int(rng.integers(len(variants)))
        text = variants[variant]
        rows = set()
        for name, spec in specs.items():
            if spec["pick"] in ("row", "row_run", "row_under") and (
                    "{" + name + "}" in text or "{" + name + "[" in text):
                picked = slots[name]
                rows |= {(spec["field"], r) for r in (picked if isinstance(picked, list) else [picked])}
        return text.format(**slots), rows

    def request(self, rng, cls: str, variant: int | None = None) -> str:
        """One request of the class, of a random variant unless one is
        named; rows zipfian by popularity."""
        return self._draw(rng, cls, variant, uniform=False)[0]

    def sweep(self, seed: int, largest: int):
        """The warm-up's first pass, (class, calls of one request).  The
        program compiles one program per query shape and per size of a
        batch rounded up to a power of two; it batches the calls of one
        request like the requests of one flight, and answers a call it has
        seen from its result cache without a launch.  So each request here
        holds 1, 2, 4, ... ``largest`` calls that were never sent before, as
        far up as there are such calls to give: of all the variants of a
        class in turn (lanes that batch across variants), then of each
        variant alone (lanes that compile per pair of fields).  The planner
        may evaluate a row that several calls share once and apart, which
        changes the batch, so each variant goes twice: with calls as they
        come, and with calls that share no row.  The same walk under every
        seed."""
        rng = np.random.default_rng([int(seed), PHASES["warm"], 0x5EE9])
        sent: set[str] = set()
        for cls, c in self.classes.items():
            alone = [[v] for v in range(len(c["variants"]))]
            passes = [(sum(alone, []), True)] if len(alone) > 1 else []
            for group in alone:
                passes.append((group, False))
                if self._draw(rng, cls, group[0], uniform=True)[1]:  # it names rows
                    passes.append((group, True))
            for group, disjoint in passes:
                k = 1
                while k <= largest:
                    calls: list[str] = []
                    rows: set = set()
                    for t in range(40 * k):
                        pql, named = self._draw(rng, cls, group[t % len(group)], uniform=True)
                        if pql not in sent and not (disjoint and named & rows):
                            sent.add(pql)
                            rows |= named
                            calls.append(pql)
                            if len(calls) == k:
                                break
                    if calls:
                        yield cls, calls
                    if len(calls) < k:
                        break
                    k *= 2

    def twins(self, seed: int, largest: int):
        """(class, calls): of every variant one request that holds one call
        twice, a call ``sweep`` has not sent, where the variant has one left.
        Two connections that send the same call into one flight before its
        answer is cached make the planner evaluate the subtree the two share
        once and apart, outside the batch lanes; a range predicate under an
        ``Intersect`` then runs through programs that no other request of
        the mix compiles.  With thresholds uniform over 20,050 values a
        window meets that about once in sixteen runs, so the warm-up has to."""
        sent = {pql for _, calls in self.sweep(seed, largest) for pql in calls}
        rng = np.random.default_rng([int(seed), PHASES["warm"], 0x2B1D])
        for cls, c in self.classes.items():
            for variant in range(len(c["variants"])):
                for _ in range(40):
                    pql = self._draw(rng, cls, variant, uniform=True)[0]
                    if pql not in sent:
                        sent.add(pql)
                        yield cls, [pql, pql]
                        break

    def slabs(self, seed: int) -> list[tuple[int, int, int]]:
        """What the write stream imports, in order: (k, shard, slab) over
        every free slab of the configuration, round-robin over the shards,
        so that any ``shards`` in a row go to distinct shards.  Every seed
        has the same schedule from another first shard; slab ``k`` of a
        phase is due ``k * every_s`` after the phase's start."""
        shards = int(self.cfg["shards"])
        first, last = slabs_per_shard(self.cfg), width_of(self.cfg) // int(self.cfg["slab_rides"])
        order = [(int(seed) + i) % shards for i in range(shards)]
        return [(k, shard, slab) for k, (slab, shard) in enumerate(
            (slab, shard) for slab in range(first, last) for shard in order)]

    def every_variant(self, seed: int, round_: int, size: int):
        """(class, calls): what the warm-up reads after the imports of round
        ``round_``, when no answer over an imported field is cached any
        more.  One request of one call of every variant of every class, so
        that every field the mix names is read in every lane the mix reads
        it in.  Then, of all the variants of a class in turn and of each
        variant alone, one request of ``size`` distinct calls, or of as many
        as the variant has left: a class with few distinct requests (21
        filtered sums) never fills a large batch in ``sweep``, where a call
        sent once is answered from the result cache for good; beside a
        stream it does, every time an import has emptied that cache.  Rows
        uniform; no call twice in a round."""
        rng = np.random.default_rng([int(seed), PHASES["warm"], int(round_), 0x1A9E])
        sent: set[str] = set()
        for cls, c in self.classes.items():
            alone = [[v] for v in range(len(c["variants"]))]
            for v in range(len(alone)):
                pql = self._draw(rng, cls, v, uniform=True)[0]
                sent.add(pql)
                yield cls, [pql]
            for group in ([sum(alone, [])] if len(alone) > 1 else []) + alone:
                calls: list[str] = []
                for t in range(40 * size):
                    pql = self._draw(rng, cls, group[t % len(group)], uniform=True)[0]
                    if pql not in sent:
                        sent.add(pql)
                        calls.append(pql)
                        if len(calls) == size:
                            break
                if calls:
                    yield cls, calls

    def stream(self, seed: int, phase: str, conn: int):
        """Endless (class, pql) for one connection."""
        rng = np.random.default_rng([int(seed), PHASES[phase], int(conn), 0x3C1])
        while True:
            for cls in (self.deck[i] for i in rng.permutation(len(self.deck))):
                yield cls, self.request(rng, cls)


def fingerprint(cfg: dict, mix: dict, seed: int, per_conn: int = 200) -> str:
    """sha256 over the first ``per_conn`` window requests of every
    connection: two builds from one seed must agree."""
    m = Mix(cfg, mix)
    h = hashlib.sha256()
    for conn in range(int(mix["connections"])):
        s = m.stream(seed, "window", conn)
        for _ in range(per_conn):
            cls, pql = next(s)
            h.update(f"{conn}\x00{cls}\x00{pql}\n".encode())
    return h.hexdigest()


def sampled(seed: int, conn: int, n: int, one_in: int) -> bool:
    """Whether request ``n`` of connection ``conn`` keeps its answer for
    the comparison: a sample drawn from the seed, one in ``one_in``."""
    h = hashlib.blake2b(f"{seed}/{conn}/{n}".encode(), digest_size=8).digest()
    return int.from_bytes(h, "little") % one_in == 0
