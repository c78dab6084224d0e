"""The comparison that decides ``correct``.

Every number compared is exact, so every limit is 0: how many sampled reads
of the window disagree with the reference, how many programs compiled
inside the window, how many requests failed.

Under a mix that only reads, every answer has one right value: the
reference's over the data the load stage imported and the server
acknowledged.  Under a mix that streams imports beside its readers a read
is held to the configuration's guarantee, "an acknowledged import is
visible to every later read", by the two logs alone (one clock,
``time.monotonic``).  Over the fields its PQL names, an import request is
*certain* for a read when it was acknowledged before the read was sent,
and *uncertain* when it was sent before the read's answer arrived and is
not certain; any other was sent after the answer and cannot be in it.

- No uncertain request: the answer is the reference's at the certain
  state, exactly.
- One to three: it is the reference's at one of the states in between,
  each uncertain request applied or not.
- More: the read is not judged.  The run logs how many; it is no number
  of ``compared``, since nothing a control breaks moves it (the controls
  answer over the run's own two logs).  What holds the judge to its work
  is ``classes_unjudged``: every class needs an answer judged exactly.

So an answer from before an acknowledged import fails, and so does one
ahead of every import that was sent.
"""

from __future__ import annotations

import itertools
import json
import math
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from datagen import UNSET
from reference import Reference, parse

# ---------------------------------------------------------------------------
# reference answers in the server's JSON form (what a control serves)
# ---------------------------------------------------------------------------


def to_json(call_name: str, raw):
    if call_name == "Count":
        return raw
    if call_name == "Sum":
        return {"value": raw[0], "count": raw[1]}
    if call_name == "TopN":
        counts, n = raw
        order = np.argsort(-counts, kind="stable")
        pairs = [{"id": int(i), "count": int(counts[i])} for i in order if counts[i] > 0]
        return pairs[:n] if n else pairs
    if call_name == "GroupBy":  # Pilosa 1.4's GroupCount: group, count and, under aggregate=Sum(field=), sum
        names, counts, *sums = raw
        listed = []
        for idx in zip(*np.nonzero(counts)):
            listed.append({"group": [{"field": f, "rowID": int(r)} for f, r in zip(names, idx)],
                           "count": int(counts[idx])})
            if sums:
                listed[-1]["sum"] = int(sums[0][idx])
        return listed
    return {"attrs": {}, "columns": [int(c) for c in raw]}


# ---------------------------------------------------------------------------
# one served answer against the reference's
# ---------------------------------------------------------------------------


def check_answer(call_name: str, got, want) -> str | None:
    """None when the served answer ``got`` (JSON form) is the reference's
    ``want`` (plain form); else what is wrong."""
    try:
        if call_name == "Count":
            ok = isinstance(got, int) and not isinstance(got, bool) and got == want
            return None if ok else f"count {got}, want {want}"
        if call_name == "Sum":
            ok = (got["value"], got["count"]) == want
            return None if ok else f"sum {got}, want {want}"
        if call_name == "TopN":
            counts, n = want
            ranked = np.sort(counts[counts > 0])[::-1][:n or None]
            if len(got) != len(ranked):
                return f"topn returned {len(got)} rows, want {len(ranked)}"
            for k, p in enumerate(got):  # ties may come in any order of ids
                if p["count"] != counts[p["id"]] or p["count"] != ranked[k]:
                    return (f"topn rank {k}: id {p['id']} count {p['count']}, want count "
                            f"{ranked[k]} (that id has {counts[p['id']]})")
            if len({p["id"] for p in got}) != len(got):
                return "topn repeats an id"
            return None
        if call_name == "GroupBy":
            names, counts, *sums = want  # sums: asked for with aggregate=Sum(field=), else a served one is ignored
            seen = np.zeros(counts.shape, np.int64)
            for g in got:
                if [x["field"] for x in g["group"]] != names:
                    return f"group fields {g['group']}"
                idx = tuple(x["rowID"] for x in g["group"])
                if g["count"] <= 0 or seen[idx]:
                    return f"group {idx} count {g['count']} (empty or repeated)"
                seen[idx] = g["count"]
                if sums:
                    want_sum = int(sums[0][idx])  # a Python integer: a served one may be past int64
                    if "sum" not in g:
                        return f"group {idx} has no sum, want {want_sum}"
                    if isinstance(g["sum"], bool) or not isinstance(g["sum"], int):
                        return f"group {idx} sum {g['sum']!r} is no integer, want {want_sum}"
                    if g["sum"] != want_sum:
                        return f"group {idx} sum {g['sum']}, want {want_sum}"
            bad = np.argwhere(seen != counts)
            if len(bad):
                idx = tuple(int(i) for i in bad[0])
                return f"group {idx} count {seen[idx]}, want {counts[idx]}"
            return None
        cols = np.asarray(got["columns"], np.int64)
        if not np.array_equal(cols, want):
            return f"{len(cols)} columns, want the reference's {len(want)}"
        return None
    except (KeyError, TypeError, IndexError, ValueError) as e:
        return f"answer of another shape ({type(e).__name__}: {e}): {str(got)[:120]}"


# ---------------------------------------------------------------------------
# the control: the reference in the program's place, one guarantee broken
# ---------------------------------------------------------------------------


class LossyReference(Reference):
    """Breaks "an acknowledged import is visible" and "answers are exact":
    a store that acknowledged every import and kept none of it in one
    column of sixteen."""

    MOD, REM = 16, 5

    def apply_slab(self, shard, slab, values):
        super().apply_slab(shard, slab, values)
        lo = slab * self.slab
        for name in values.keys() & self.one.keys():
            blank = -1 if self.fields[name]["kind"] == "int" else UNSET
            self.one[name][shard, lo + self.REM:lo + self.slab:self.MOD] = blank


class StaleReference(Reference):
    """Breaks "an acknowledged import is visible to every later read":
    every answer is exact for a state the store once had, but a streamed
    import shows only when the same field's next one has been acknowledged,
    one slab late.  (``judge_reads`` hands it the imports as they become
    certain.)"""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._held: dict[str, dict] = {}

    def apply_import(self, imp):
        shown = self._held.get(imp["field"])
        self._held[imp["field"]] = imp
        if shown is not None:
            super().apply_import(shown)


CONTROLS = {"lossy": LossyReference, "stale": StaleReference}
UNCERTAIN_MAX = 3  # 2^3 states a read at the most
JUDGE_THREADS = 4  # the reference is numpy over whole columns and lets the interpreter go
ANSWERS_AHEAD = 256  # as many as ``Reference.answer`` remembers before it forgets them all


# ---------------------------------------------------------------------------
# the walk over the window's sample
# ---------------------------------------------------------------------------


def by_ack(imports: list[dict]) -> list[dict]:
    """The import log in the order of its acknowledgements; a request that
    failed was never acknowledged."""
    return sorted(imports, key=lambda m: m["t_ack"] if m["status"] == 200 else math.inf)


def _served(r: dict):
    try:
        return json.loads(r["body"])["results"][0]
    except (ValueError, KeyError, IndexError, TypeError):
        return None


def _between(ref: Reference, name: str, got, pql: str, uncertain: list[dict]) -> str | None:
    """None when ``got`` is the reference's answer at the present state
    with some of ``uncertain`` applied; else what is wrong with it at the
    present state."""
    first = None
    n = len(uncertain)
    for size in range(n + 1):
        for some in itertools.combinations(range(n), size):
            for i in some:
                ref.apply_import(uncertain[i])
            why = check_answer(name, got, ref.answer(pql))
            for i in some:
                ref.revert_import(uncertain[i])
            if why is None:
                return None
            first = first or why
    return first if not n else f"{first} (nor with any of {n} imports in flight)"


def judge_reads(ref: Reference, reads: list[dict], imports: list[dict] = (),
                control: Reference | None = None) -> dict:
    """``reads``: cls, pql, body (the served JSON bytes) and, where there
    are ``imports`` (field, shard, slab, t_send, t_ack, status), t_send and
    t_recv.  ``ref`` is at the state of the load and is left at the certain
    state of the last read.  With ``control`` the control's answers stand in
    for the served ones."""
    mismatches: list[str] = []
    per_class: dict[str, int] = {}  # answers judged exactly: nothing in flight beside them
    in_flight: dict[int, int] = {}
    unjudged = 0
    log = by_ack(imports)
    done = 0  # log[:done] are certain
    if log:
        reads = sorted(reads, key=lambda r: r["t_send"])
    else:  # one state for every read: the answers ahead of the walk, numpy beside numpy
        with ThreadPoolExecutor(JUDGE_THREADS) as pool:
            for who in filter(None, (ref, control)):
                list(pool.map(who.answer, sorted({r["pql"] for r in reads})[:ANSWERS_AHEAD]))
    for r in reads:
        while done < len(log) and log[done]["status"] == 200 and log[done]["t_ack"] < r["t_send"]:
            ref.apply_import(log[done])
            if control is not None:
                control.apply_import(log[done])
            done += 1
        call, named = ref.call(r["pql"])
        uncertain = [m for m in log[done:] if m["t_send"] < r["t_recv"] and m["field"] in named]
        in_flight[len(uncertain)] = in_flight.get(len(uncertain), 0) + 1
        if len(uncertain) > UNCERTAIN_MAX:
            unjudged += 1
            continue
        got = _served(r) if control is None else to_json(call.name, control.answer(r["pql"]))
        if not uncertain:
            per_class[r["cls"]] = per_class.get(r["cls"], 0) + 1
        why = _between(ref, call.name, got, r["pql"], sorted(uncertain, key=lambda m: m["t_send"]))
        if why is not None:
            mismatches.append(f"{r['cls']}: {r['pql']}: {why}"[:300])
    return {"compared": len(reads) - unjudged, "mismatches": len(mismatches), "unjudged": unjudged,
            "per_class": per_class, "in_flight": dict(sorted(in_flight.items())),
            "certain": done, "examples": mismatches[:8]}


def judge_readback(ref: Reference, imports: list[dict], answers: list[dict]) -> dict:
    """``answers`` (pql, body) were asked after the last acknowledgement:
    each is the reference's with every acknowledged import applied."""
    for m in imports:
        if m["status"] == 200 and (m["shard"], m["slab"]) not in ref.applied.get(m["field"], ()):
            ref.apply_import(m)
    mismatches = []
    for r in answers:
        call, _ = ref.call(r["pql"])
        why = check_answer(call.name, _served(r), ref.answer(r["pql"]))
        if why is not None:
            mismatches.append(f"read-back: {r['pql']}: {why}"[:300])
    return {"compared": len(answers), "mismatches": len(mismatches), "examples": mismatches[:8]}
