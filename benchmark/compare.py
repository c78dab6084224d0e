"""The comparison that decides ``correct``.

Every number compared is exact, so every limit is 0: how many sampled
reads of the window disagree with the reference, how many programs
compiled inside the window, how many requests failed.  The mixes of the
grid only read, so every answer has one right value: the reference's over
the data the load stage imported and the server acknowledged.
"""

from __future__ import annotations

import json

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
    if call_name == "GroupBy":
        names, counts = raw
        return [
            {"group": [{"field": f, "rowID": int(r)} for f, r in zip(names, idx)],
             "count": int(counts[idx])}
            for idx in zip(*np.nonzero(counts))
        ]
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
            names, counts = want
            seen = np.zeros(counts.shape, np.int64)
            for g in got:
                if [x["field"] for x in g["group"]] != names:
                    return f"group fields {g['group']}"
                idx = tuple(x["rowID"] for x in g["group"])
                if g["count"] <= 0 or seen[idx]:
                    return f"group {idx} count {g['count']} (empty or repeated)"
                seen[idx] = g["count"]
            bad = np.argwhere(seen != counts)
            if len(bad):
                idx = tuple(bad[0])
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
        for name in values:
            blank = -1 if self.fields[name]["kind"] == "int" else UNSET
            self.one[name][shard, lo + self.REM:lo + self.slab:self.MOD] = blank


CONTROLS = {"lossy": LossyReference}


# ---------------------------------------------------------------------------
# the walk over the window's sample
# ---------------------------------------------------------------------------


def judge_reads(ref: Reference, reads: list[dict], control: Reference | None = None) -> dict:
    """``reads``: cls, pql, body (the served JSON bytes).  With ``control``
    the control's answers stand in for the served ones."""
    mismatches: list[str] = []
    per_class: dict[str, int] = {}
    for r in reads:
        name = parse(r["pql"]).name
        if control is not None:
            got = to_json(name, control.answer(r["pql"]))
        else:
            try:
                got = json.loads(r["body"])["results"][0]
            except (ValueError, KeyError, IndexError, TypeError):
                got = None
        per_class[r["cls"]] = per_class.get(r["cls"], 0) + 1
        why = check_answer(name, got, ref.answer(r["pql"]))
        if why is not None:
            mismatches.append(f"{r['cls']}: {r['pql']}: {why}"[:300])
    return {"compared": len(reads), "mismatches": len(mismatches),
            "per_class": per_class, "examples": mismatches[:8]}
