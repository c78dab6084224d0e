"""The plain reference: the same PQL semantics in numpy.

Independent of ``pilosa_tpu``: it parses the PQL text the generator sent
with a small parser of its own and evaluates it over data it generated
itself from the seed (``datagen``): one row id (set field) or one value
(int field) per column.

It answers at a *state*: the load, and on top of it the streamed imports
(one field of one slab of one shard each, ``apply_import``) that have been
applied and not taken back (``revert_import``).  A mix that streams nothing
never leaves the state of the load.
"""

from __future__ import annotations

import re

import numpy as np

from datagen import UNSET, gen_slab, slabs_per_shard, stored_fields, width_of

# ---------------------------------------------------------------------------
# PQL: the calls the traffic mixes use
# ---------------------------------------------------------------------------

_TOKEN = re.compile(
    r"\s*(?:(?P<ts>\d{4}-\d\d-\d\dT\d\d:\d\d)|(?P<num>-?\d+)|(?P<id>[A-Za-z_][A-Za-z0-9_-]*)"
    r"|(?P<op>><|<=|>=|==|!=|[=<>(),\[\]]))"
)


class Call:
    """``Name(positional..., key=value..., field <op> value)``."""

    def __init__(self, name: str):
        self.name = name
        self.pos: list = []       # Calls, ints, field names, timestamps
        self.kw: dict = {}        # key -> value
        self.cond: tuple | None = None  # (field, [(op, value), ...])

    def __repr__(self) -> str:
        return f"Call({self.name}, {self.pos}, {self.kw}, {self.cond})"


def tokenize(text: str) -> list[tuple[str, object]]:
    out, i = [], 0
    text = text.strip()
    while i < len(text):
        m = _TOKEN.match(text, i)
        if m is None:
            raise ValueError(f"cannot read PQL at {text[i:i + 20]!r}")
        kind = m.lastgroup
        val = m.group(kind)
        out.append((kind, int(val) if kind == "num" else val))
        i = m.end()
    return out


def parse(text: str) -> Call:
    toks = tokenize(text)
    call, i = _parse_call(toks, 0)
    if i != len(toks):
        raise ValueError(f"trailing PQL in {text!r}")
    return call


_CMP = {"<", ">", "<=", ">=", "==", "!=", "><"}
_FLIP = {"<": ">", ">": "<", "<=": ">=", ">=": "<="}


def _parse_value(toks, i):
    kind, val = toks[i]
    if kind == "op" and val == "[":
        items = []
        i += 1
        while toks[i] != ("op", "]"):
            if toks[i] != ("op", ","):
                items.append(toks[i][1])
            i += 1
        return items, i + 1
    if kind == "id" and i + 1 < len(toks) and toks[i + 1] == ("op", "("):
        return _parse_call(toks, i)
    return val, i + 1


def _parse_call(toks, i):
    kind, name = toks[i]
    if kind != "id" or toks[i + 1] != ("op", "("):
        raise ValueError(f"expected a call at token {i}")
    call = Call(name)
    i += 2
    while toks[i] != ("op", ")"):
        if toks[i] == ("op", ","):
            i += 1
            continue
        first, j = _parse_value(toks, i)
        nxt = toks[j]
        if nxt == ("op", "=") and toks[i][0] == "id":
            val, j = _parse_value(toks, j + 1)
            call.kw[first] = val
        elif nxt[0] == "op" and nxt[1] in _CMP:
            if toks[i][0] == "id":  # field <op> value
                val, j = _parse_value(toks, j + 1)
                call.cond = (first, [(nxt[1], val)])
            else:  # value <op> field <op> value
                field = toks[j + 1][1]
                op2 = toks[j + 2][1]
                val2, j = _parse_value(toks, j + 3)
                call.cond = (field, [(_FLIP[nxt[1]], first), (op2, val2)])
        else:
            call.pos.append(first)
        i = j
    return call, i + 1


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------


class Reference:
    def __init__(self, cfg: dict, seed: int, extent: int | None = None):
        """``extent``: the columns of a shard it holds; the load's
        ``columns`` unless a stream writes past them."""
        self.cfg, self.seed = cfg, int(seed)
        self.width = width_of(cfg)
        self.shards = int(cfg["shards"])
        self.slab = int(cfg["slab_rides"])
        self.hi = int(cfg["columns"] if extent is None else extent)
        self.fields = {f["name"]: f for f in stored_fields(cfg)}  # what the index has
        # [shards, columns]: the row id (set field) or the value (int field) of each column
        self.one: dict[str, np.ndarray] = {
            name: (np.full((self.shards, self.hi), -1, np.int32) if f["kind"] == "int"
                   else np.full((self.shards, self.hi), UNSET, np.uint16))
            for name, f in self.fields.items()}
        self._answers: dict = {}
        self._calls: dict[str, tuple[Call, tuple]] = {}
        # the state: per field, the streamed (shard, slab) applied on top of the load
        self.applied: dict[str, frozenset] = {}
        self._slabs: dict[tuple[int, int], dict] = {}

    def apply_slab(self, shard: int, slab: int, values: dict) -> None:
        lo = slab * self.slab
        for name, v in values.items():
            if name in self.one:  # a slab holds the generated-only fields too
                self.one[name][shard, lo:lo + self.slab] = v

    def _slab(self, shard: int, slab: int) -> dict:
        """A streamed slab's values; imports come a slab at a time, so the
        last few are kept."""
        key = (shard, slab)
        if key not in self._slabs:
            if len(self._slabs) >= 4:
                self._slabs.pop(next(iter(self._slabs)))
            self._slabs[key] = gen_slab(self.cfg, self.seed, shard, slab)
        return self._slabs[key]

    def apply_import(self, imp: dict) -> None:
        """One streamed import request (``field``, ``shard``, ``slab``)
        becomes part of the state."""
        name, key = imp["field"], (imp["shard"], imp["slab"])
        self.apply_slab(*key, {name: self._slab(*key)[name]})
        self.applied[name] = self.applied.get(name, frozenset()) | {key}

    def revert_import(self, imp: dict) -> None:
        """Take back an import that ``apply_import`` applied: its columns
        were free before it."""
        name, key = imp["field"], (imp["shard"], imp["slab"])
        self.apply_slab(*key, {name: -1 if self.fields[name]["kind"] == "int" else UNSET})
        self.applied[name] = self.applied[name] - {key}

    def load(self) -> None:
        """The load stage's data: every slab of every shard."""
        for shard in range(self.shards):
            for slab in range(slabs_per_shard(self.cfg)):
                self.apply_slab(shard, slab, gen_slab(self.cfg, self.seed, shard, slab))

    # -- reads -------------------------------------------------------------

    def bitmap(self, c: Call) -> np.ndarray:
        """A bitmap call -> bool [shards, columns]."""
        if c.name == "Row" or c.name == "Range":
            if c.cond is not None:
                name, conds = c.cond
                v = self.one[name]
                out = v >= 0
                for op, x in conds:
                    out &= _compare(v, op, x)
                return out
            (name, row), = c.kw.items()
            return self.one[name] == row
        if c.name not in ("Intersect", "Union", "Difference", "Xor"):
            raise ValueError(f"no reference for {c.name}")
        parts = [self.bitmap(p) for p in c.pos]
        out = parts[0]
        for p in parts[1:]:
            if c.name == "Intersect":
                out = out & p
            elif c.name == "Union":
                out = out | p
            elif c.name == "Difference":
                out = out & ~p
            else:
                out = out ^ p
        return out

    def row_counts(self, name: str, filt: np.ndarray | None) -> np.ndarray:
        v = self.one[name] if filt is None else self.one[name][filt]
        n = int(self.fields[name]["rows"])
        return np.bincount(v.ravel(), minlength=UNSET + 1)[:n].astype(np.int64)

    def call(self, pql: str) -> tuple[Call, tuple]:
        """The parsed PQL text and the fields it reads, sorted."""
        if pql not in self._calls:
            if len(self._calls) > 4096:
                self._calls.clear()
            c = parse(pql)
            self._calls[pql] = c, tuple(sorted(_names(c) & set(self.fields)))
        return self._calls[pql]

    def answer(self, pql: str):
        """``evaluate`` of the PQL text at the present state, remembered
        per text and state of the fields it reads: a dashboard repeats its
        unparametrised panels."""
        c, named = self.call(pql)
        key = (pql, tuple(self.applied.get(f) or None for f in named))
        if key not in self._answers:
            if len(self._answers) > 512:
                self._answers.clear()
            self._answers[key] = self.evaluate(c)
        return self._answers[key]

    def evaluate(self, c: Call):
        """The call's answer in a plain form ``compare`` understands:
        Count -> int; Sum -> (value, count); TopN -> (per-row counts, n);
        GroupBy -> (field names, counts by combined row ids), and with
        ``aggregate=Sum(field=<int field>)`` a third member, the field's
        values added up by the same ids (int64, exact); a bitmap call ->
        sorted column ids."""
        if c.name == "Count":
            return int(np.count_nonzero(self.bitmap(c.pos[0])))
        if c.name == "Sum":
            v = self.one[c.kw["field"]]
            m = v >= 0
            if c.pos:
                m &= self.bitmap(c.pos[0])
            return int(v[m].sum(dtype=np.int64)), int(np.count_nonzero(m))
        if c.name == "TopN":
            filt = self.bitmap(c.pos[1]) if len(c.pos) > 1 else None
            return self.row_counts(c.pos[0], filt), c.kw.get("n", 0)
        if c.name == "GroupBy":
            summed = _summed_field(c, self.fields)  # an aggregate it does not know is refused before any work
            names = [r.pos[0] for r in c.pos]
            sizes = [int(self.fields[n]["rows"]) for n in names]
            valid = self.bitmap(c.kw["filter"]) if "filter" in c.kw else None
            for n in names:
                v = self.one[n]
                valid = (v != UNSET) if valid is None else valid & (v != UNSET)
            at = np.flatnonzero(valid.ravel())  # the filter first: the groups are counted over its columns only
            code = np.zeros(len(at), np.int64)
            for n, size in zip(names, sizes):
                code = code * size + self.one[n].ravel()[at]
            groups = int(np.prod(sizes))
            counts = np.bincount(code, minlength=groups).reshape(sizes)
            if summed is None:
                return names, counts
            # a group's count is its columns under the filter, valued or not; its sum is over the valued
            v = self.one[summed].ravel()[at].astype(np.int64)
            held = v >= 0
            return names, counts, _group_sums(code[held], v[held], groups).reshape(sizes)
        cols = np.flatnonzero(self.bitmap(c).ravel())
        # [shards, columns] -> global column ids
        return (cols // self.hi) * self.width + cols % self.hi


def _names(c: Call) -> set:
    """Every identifier of a call that can name a field: the keys of a
    ``Row``, the field of a condition, of ``Sum(field=)`` (also where it is
    a ``GroupBy``'s ``aggregate``), of ``TopN`` and ``Rows``; the caller
    keeps those that are fields."""
    out = set(c.kw) | {v for v in c.kw.values() if isinstance(v, str)}
    out |= {p for p in c.pos if isinstance(p, str)}
    if c.cond is not None:
        out.add(c.cond[0])
    for p in [*c.pos, *c.kw.values()]:
        if isinstance(p, Call):
            out |= _names(p)
    return out


def _summed_field(c: Call, fields: dict) -> str | None:
    """The int field a ``GroupBy`` adds up beside each group's count:
    ``aggregate=Sum(field=<int field of the index>)`` and nothing else in
    the ``Sum``; None where the call has no ``aggregate``."""
    agg = c.kw.get("aggregate")
    if agg is None:
        return None
    if (not isinstance(agg, Call) or agg.name != "Sum" or agg.pos or agg.cond is not None
            or set(agg.kw) != {"field"} or not isinstance(agg.kw["field"], str)
            or fields.get(agg.kw["field"], {}).get("kind") != "int"):
        raise ValueError(f"no reference for GroupBy(aggregate={agg!r})")
    return agg.kw["field"]


def _group_sums(code: np.ndarray, values: np.ndarray, groups: int) -> np.ndarray:
    """``values`` (int64) added up by ``code`` -> int64 [groups].  Integer
    adds over the sorted codes: ``np.bincount(weights=)`` adds in float64,
    which is exact only under 2^53."""
    sums = np.zeros(groups, np.int64)
    if len(code):
        order = np.argsort(code, kind="stable")
        code, values = code[order], values[order]
        starts = np.flatnonzero(np.concatenate(([True], code[1:] != code[:-1])))
        sums[code[starts]] = np.add.reduceat(values, starts)
    return sums


def _compare(v: np.ndarray, op: str, x) -> np.ndarray:
    if op == "<":
        return v < x
    if op == "<=":
        return v <= x
    if op == ">":
        return v > x
    if op == ">=":
        return v >= x
    if op == "==":
        return v == x
    if op == "!=":
        return v != x
    if op == "><":
        return (v >= x[0]) & (v <= x[1])
    raise ValueError(op)
