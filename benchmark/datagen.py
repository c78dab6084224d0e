"""Seeded data for a configuration, and the client-side roaring encoder.

Everything is a function of ``(config, seed, shard, slab)``: the loaders
and the reference each call these functions and never hand data to one
another.  Imports nothing from the program.

A *slab* is ``slab_rides`` consecutive columns of one shard; a shard holds
``columns // slab_rides`` of them, and the load stage imports them all.

A set field's file entry says how its rows share the columns (``rows`` is
the id space, every column has exactly one row):

``weights``     the shares, one per row id
``uniform``     true: every row id as likely, drawn as an integer (no table to search)
``zipf``        theta of a zipfian tail over the row ids, 0 the most common
``lognormal``   [mu, sigma]: the value rounded to the nearest row id
``scatter``     with ``zipf`` and ``present``: only ``present`` of the
                ``rows`` ids ever occur, zipfian by rank, the ranks dealt
                over the id space by a permutation fixed in the file
``min_share``   a floor under every occurring row's share, so that each
                occurs under every seed and the program's stack shapes (one
                slot per row that occurs) are the same for every seed

``follows``     the field's row id is a fixed many-to-one map of another
                set field's (its *parent*): ``div`` (parent id // div) or
                ``map`` (one id per parent id, listed); nothing is drawn.  A
                parent may follow another in turn (city -> nation -> region),
                and the field gets the shares its parent implies

An int field is ``lognormal`` itself, ``uniform`` ([lo, hi], both ends in),
``from_rows`` (the row id of a set field times ``scale`` plus a uniform
``jitter``, so the two agree), a ``product`` of two generated values, or a
value ``scaled``: ``{"field": a, "less_pct": b}`` is ``a * (100 - b) // 100``,
``b`` another generated value or a number.

``"stored": false`` on a field of either kind: generated, so that others may
follow or multiply it, but no field of the index (the day an order was made,
the price of a part): absent from the schema, the load and the reference.
"""

from __future__ import annotations

import json
import struct
from math import erf, log, sqrt

import numpy as np

UNSET = 65535  # a set field holds one row id per column (uint16), or this

# Pilosa's roaring file format (the cookie import-roaring accepts)
_MAGIC = 12348
_ARRAY, _BITMAP = 1, 2
_ARRAY_MAX = 4096

BSI_EXISTS_ROW, BSI_OFFSET_ROW = 0, 2  # bsig_<field> view: exists, sign, planes


def width_of(cfg: dict) -> int:
    return 1 << int(cfg["shard_width_exp"])


def fields_by_name(cfg: dict) -> dict:
    return {f["name"]: f for f in cfg["fields"]}


def stored_fields(cfg: dict) -> list[dict]:
    """The fields the index has: all but the generated-only ones."""
    return [f for f in cfg["fields"] if f.get("stored", True)]


def slabs_per_shard(cfg: dict) -> int:
    return int(cfg["columns"]) // int(cfg["slab_rides"])


def _lognormal_bins(n: int, mu: float, sigma: float) -> np.ndarray:
    """Share of a lognormal that rounds to each of the ids 0..n-1 (the
    mass above n - 0.5 goes to the last)."""
    def cdf(x: float) -> float:
        return 0.0 if x <= 0 else 0.5 * (1.0 + erf((log(x) - mu) / (sigma * sqrt(2.0))))
    edges = [cdf(k + 0.5) for k in range(n)]
    edges[-1] = 1.0
    return np.diff([0.0] + edges)


_WEIGHTS: dict[str, np.ndarray] = {}


def row_map(field: dict, fields: dict) -> np.ndarray:
    """Of a field that ``follows`` another: its row id for each row id of
    the parent."""
    how = field["follows"]
    parent_rows = int(fields[how["field"]]["rows"])
    if "map" in how:
        m = np.asarray(how["map"], np.int64)
    else:
        m = np.arange(parent_rows, dtype=np.int64) // int(how["div"])
    if len(m) != parent_rows or m.min() < 0 or m.max() >= int(field["rows"]):
        raise ValueError(f"{field['name']}: a map of {len(m)} ids up to {m.max()} for "
                         f"{parent_rows} rows of {how['field']} and {field['rows']} of its own")
    return m


def ancestor_map(fields: dict, name: str, ancestor: str) -> np.ndarray:
    """For each row id of the set field ``name``, the row id of ``ancestor``,
    a field that follows it, directly or through others."""
    chain = [ancestor]  # from the ancestor down to ``name``
    while chain[-1] != name:
        how = fields[chain[-1]].get("follows")
        if how is None or how["field"] in chain:
            raise ValueError(f"{ancestor} does not follow {name}")
        chain.append(how["field"])
    out = np.arange(int(fields[name]["rows"]), dtype=np.int64)
    for follower in reversed(chain[:-1]):
        out = row_map(fields[follower], fields)[out]
    return out


def _chain_key(field: dict, fields: dict | None) -> str:
    key = json.dumps(field, sort_keys=True)
    if "follows" in field:
        key += _chain_key(fields[field["follows"]["field"]], fields)
    return key


def row_weights(field: dict, fields: dict | None = None) -> np.ndarray:
    """The share of the columns that each row id of a set field gets; 0
    for an id that never occurs.  ``fields`` (name -> entry) where the
    field follows another: it gets the shares its parent implies."""
    key = _chain_key(field, fields)
    if key not in _WEIGHTS:
        _WEIGHTS[key] = _row_weights(field, fields)
    return _WEIGHTS[key]


def _row_weights(field: dict, fields: dict | None) -> np.ndarray:
    n = int(field["rows"])
    if "follows" in field:
        parent = fields[field["follows"]["field"]]
        return np.bincount(row_map(field, fields), weights=row_weights(parent, fields), minlength=n)
    if field.get("uniform"):
        w = np.ones(n)
    elif "weights" in field:
        w = np.asarray(field["weights"], np.float64)
        if len(w) != n:
            raise ValueError(f"{field['name']}: {len(w)} weights for {n} rows")
    elif "lognormal" in field:
        w = _lognormal_bins(n, *field["lognormal"])
    elif "scatter" in field:
        present = int(field["present"])
        ids = np.random.default_rng(int(field["scatter"])).permutation(n)[:present]
        w = np.zeros(n)
        w[ids] = np.arange(1, present + 1, dtype=np.float64) ** -float(field["zipf"])
    else:
        w = np.arange(1, n + 1, dtype=np.float64) ** -float(field["zipf"])
    w = w / w.sum()
    if "min_share" in field:
        w = np.where(w > 0, np.maximum(w, float(field["min_share"])), 0.0)
        w = w / w.sum()
    return w


def popularity_order(field: dict, fields: dict | None = None) -> np.ndarray:
    """The row ids that occur, the most common first (what a zipfian
    query draw ranks)."""
    w = row_weights(field, fields)
    return np.argsort(-w, kind="stable")[:int(np.count_nonzero(w))]


def gen_slab(cfg: dict, seed: int, shard: int, slab: int) -> dict[str, np.ndarray]:
    """Values of every field for the ``slab_rides`` columns of one slab:
    ``{field: array}``, uint16 row ids for set fields and int32 values for
    int fields; the fields that are not stored among them.  Only a field's
    own draw takes from the generator, in the file's order (set fields, then
    int fields): what follows, multiplies or scales another draws nothing."""
    rng = np.random.default_rng([int(seed), int(shard), int(slab), 0x7A21])
    n = int(cfg["slab_rides"])
    fields = fields_by_name(cfg)
    out: dict[str, np.ndarray] = {}
    for f in cfg["fields"]:
        if f["kind"] == "set" and f.get("uniform"):
            out[f["name"]] = rng.integers(0, int(f["rows"]), n).astype(np.uint16)
        elif f["kind"] == "set" and "follows" not in f:
            cdf = np.cumsum(row_weights(f))
            cdf[-1] = 1.0
            out[f["name"]] = np.searchsorted(cdf, rng.random(n), side="right").astype(np.uint16)
    todo = [f for f in cfg["fields"] if f["kind"] == "set" and "follows" in f]
    while todo:  # a follower after its parent, however the file orders them
        ready = [f for f in todo if f["follows"]["field"] in out]
        if not ready:
            raise ValueError(f"{todo[0]['name']} follows {todo[0]['follows']['field']}, which is never made")
        for f in ready:
            out[f["name"]] = row_map(f, fields)[out[f["follows"]["field"]]].astype(np.uint16)
        todo = [f for f in todo if f["name"] not in out]
    for f in cfg["fields"]:
        if f["kind"] != "int":
            continue
        if "from_rows" in f:
            src = f["from_rows"]
            lo, hi = src["jitter"]
            v = out[src["field"]].astype(np.int64) * int(src["scale"]) + rng.integers(lo, hi + 1, n)
        elif "uniform" in f:
            lo, hi = f["uniform"]
            v = rng.integers(lo, hi + 1, n)
        elif "product" in f:  # of generated values that stand before it in the file
            a, b = f["product"]
            v = out[a].astype(np.int64) * out[b].astype(np.int64)
        elif "scaled" in f:
            less = f["scaled"]["less_pct"]
            less = out[less].astype(np.int64) if isinstance(less, str) else int(less)
            v = out[f["scaled"]["field"]].astype(np.int64) * (100 - less) // 100
        else:
            mu, sigma = f["lognormal"]
            v = np.rint(np.exp(rng.normal(mu, sigma, n)))
        out[f["name"]] = np.clip(v, f["min"], f["max"]).astype(np.int32)
    return out


# ---------------------------------------------------------------------------
# positions and roaring bytes
# ---------------------------------------------------------------------------


def set_positions(values: np.ndarray, col0: int, width: int) -> np.ndarray:
    """Sorted ``row * width + column`` of a categorical slab that starts
    at shard-local column ``col0``."""
    order = np.argsort(values, kind="stable")
    rows = values[order].astype(np.uint64)
    return rows * np.uint64(width) + (order.astype(np.uint64) + np.uint64(col0))


def bsi_positions(values: np.ndarray, col0: int, width: int, depth: int) -> np.ndarray:
    """Sorted positions of the ``bsig_`` view rows for non-negative
    ``values``: the exists row, then one row per set bit plane."""
    cols = np.arange(len(values), dtype=np.uint64) + np.uint64(col0)
    parts = [np.uint64(BSI_EXISTS_ROW * width) + cols]
    for k in range(depth):
        parts.append(np.uint64((BSI_OFFSET_ROW + k) * width) + cols[(values >> k) & 1 == 1])
    return np.concatenate(parts)


def encode_roaring(positions: np.ndarray) -> bytes:
    """Sorted, unique uint64 positions -> Pilosa roaring bytes (array
    containers up to 4096 values, bitmap containers above)."""
    positions = np.asarray(positions, np.uint64)
    keys = positions >> np.uint64(16)
    lows = (positions & np.uint64(0xFFFF)).astype("<u2")
    ukeys, starts, counts = np.unique(keys, return_index=True, return_counts=True)
    header, datas = [], []
    for key, s, n in zip(ukeys.tolist(), starts.tolist(), counts.tolist()):
        vals = lows[s:s + n]
        if n <= _ARRAY_MAX:
            header.append(struct.pack("<QHH", key, _ARRAY, n - 1))
            datas.append(vals.tobytes())
        else:
            bits = np.zeros(1 << 16, np.uint8)
            bits[vals] = 1
            header.append(struct.pack("<QHH", key, _BITMAP, n - 1))
            datas.append(np.packbits(bits, bitorder="little").tobytes())
    count = len(datas)
    out = [struct.pack("<II", _MAGIC, count), *header]
    offset = 8 + count * 16
    for d in datas:
        out.append(struct.pack("<I", offset))
        offset += len(d)
    return b"".join(out + datas)


def bit_depth(field: dict) -> int:
    return max(int(field["max"]).bit_length(), 1)


def slab_import(cfg: dict, field: dict, shard_values: np.ndarray, col0: int) -> tuple[str, bytes, int]:
    """(query string, body, bits) of one import-roaring request carrying
    ``shard_values`` of ``field`` from shard-local column ``col0``."""
    width = width_of(cfg)
    if field["kind"] == "int":
        pos = bsi_positions(shard_values, col0, width, bit_depth(field))
        return f"?view=bsig_{field['name']}", encode_roaring(pos), len(pos)
    pos = set_positions(shard_values, col0, width)
    return "", encode_roaring(pos), len(pos)
