"""Query result types (reference: row.go Row, pilosa.go Pair/ValCount/
GroupCount/RowIdentifiers and internal/public.proto QueryResult union).

``Row`` is the cross-shard bitmap result: one device word-vector per shard
(the analogue of the reference's ordered rowSegments, row.go:332-344). Set
algebra stays on device; column ids materialize on host only at the API
edge (row.go Columns)."""

from __future__ import annotations

from dataclasses import dataclass, field as dc_field
from typing import Any

import numpy as np

import jax.numpy as jnp

from pilosa_tpu.ops import bitops


class Row:
    """Cross-shard bitmap result."""

    def __init__(self, segments: dict[int, Any] | None = None, n_words: int | None = None):
        # shard -> uint32[W] device array
        self.segments: dict[int, Any] = segments or {}
        self.n_words = n_words
        self.attrs: dict[str, Any] = {}
        self.keys: list[str] | None = None

    def _words(self, shard: int, like) -> Any:
        seg = self.segments.get(shard)
        if seg is None:
            if isinstance(like, np.ndarray):
                return np.zeros_like(like)
            return jnp.zeros_like(like)
        return seg

    def shards(self) -> list[int]:
        return sorted(self.segments)

    # -- set algebra (reference row.go:107-239) -----------------------------

    def intersect(self, other: "Row") -> "Row":
        out = {}
        for shard in set(self.segments) & set(other.segments):
            out[shard] = self.segments[shard] & other.segments[shard]
        return Row(out, self.n_words or other.n_words)

    def union(self, other: "Row") -> "Row":
        out = dict(self.segments)
        for shard, seg in other.segments.items():
            out[shard] = (out[shard] | seg) if shard in out else seg
        return Row(out, self.n_words or other.n_words)

    def difference(self, other: "Row") -> "Row":
        out = {}
        for shard, seg in self.segments.items():
            o = other.segments.get(shard)
            out[shard] = seg if o is None else seg & ~o
        return Row(out, self.n_words or other.n_words)

    def xor(self, other: "Row") -> "Row":
        out = dict(self.segments)
        for shard, seg in other.segments.items():
            out[shard] = (out[shard] ^ seg) if shard in out else seg
        return Row(out, self.n_words or other.n_words)

    def shift(self, n: int = 1) -> "Row":
        """Per-shard shift (no cross-shard carry, matching the reference's
        per-shard Shift semantics, roaring.go:944)."""
        out = {
            shard: (
                bitops.shift_row_host(seg, n)
                if isinstance(seg, np.ndarray)
                else bitops.shift_row(seg, n)
            )
            for shard, seg in self.segments.items()
        }
        return Row(out, self.n_words)

    # -- materialization ----------------------------------------------------
    #
    # Segments are either device arrays (throughput-tier results) or
    # host numpy arrays (latency-tier results served from the fragment
    # mirrors); counts dispatch per segment so a host-tier Row never
    # pays a device round trip.

    @staticmethod
    def _seg_count(seg) -> int:
        if isinstance(seg, np.ndarray):
            return bitops.popcount_host(seg)
        return int(bitops.count_bits(seg))

    def count(self) -> int:
        """Python-int exact total (per-shard int32 partials summed host
        side, so >2^31 totals are safe)."""
        total = 0
        device = []
        for seg in self.segments.values():
            if isinstance(seg, np.ndarray):
                total += bitops.popcount_host(seg)
            else:
                device.append(seg)
        if device:
            # a device lane's segments: launch every count, then wait for
            # them under the kernels' spans like every other device result
            from pilosa_tpu.ops import kernels

            with kernels.enqueue("count_bits"):
                parts = [bitops.count_bits(seg) for seg in device]
            total += sum(int(kernels.pull(p, "count_bits")) for p in parts)
        return total

    def intersection_count(self, other: "Row") -> int:
        total = 0
        for shard in set(self.segments) & set(other.segments):
            a, b = self.segments[shard], other.segments[shard]
            if isinstance(a, np.ndarray) and isinstance(b, np.ndarray):
                total += bitops.pair_count_host(a, b, "intersect")
            else:
                total += int(bitops.intersection_count(a, b))
        return total

    def is_empty(self) -> bool:
        return all(self._seg_count(s) == 0 for s in self.segments.values())

    def columns(self) -> np.ndarray:
        """Absolute sorted column ids (host materialization at the API
        edge)."""
        parts = []
        for shard in self.shards():
            words = np.asarray(self.segments[shard])
            width = len(words) * 32
            offs = bitops.unpack_columns(words)
            parts.append(offs + np.uint64(shard) * np.uint64(width))
        if not parts:
            return np.array([], dtype=np.uint64)
        return np.concatenate(parts)

    def to_dict(self) -> dict:
        d: dict[str, Any] = {"attrs": self.attrs}
        if self.keys is not None:
            d["keys"] = self.keys
        else:
            d["columns"] = [int(c) for c in self.columns()]
        return d


@dataclass
class ValCount:
    """Sum/Min/Max result (reference pilosa.go ValCount)."""

    value: int = 0
    count: int = 0

    def to_dict(self) -> dict:
        return {"value": self.value, "count": self.count}


@dataclass
class Pair:
    """TopN entry (reference pilosa.go Pair)."""

    id: int = 0
    key: str | None = None
    count: int = 0

    def to_dict(self) -> dict:
        d: dict[str, Any] = {"count": self.count}
        if self.key is not None:
            d["key"] = self.key
        else:
            d["id"] = self.id
        return d


@dataclass
class RowIdentifiers:
    """Rows() result (reference pilosa.go RowIdentifiers)."""

    rows: list[int] = dc_field(default_factory=list)
    keys: list[str] | None = None

    def to_dict(self) -> dict:
        if self.keys is not None:
            return {"keys": self.keys}
        return {"rows": self.rows}


@dataclass
class FieldRow:
    field: str
    row_id: int = 0
    row_key: str | None = None

    def to_dict(self) -> dict:
        d: dict[str, Any] = {"field": self.field}
        if self.row_key is not None:
            d["rowKey"] = self.row_key
        else:
            d["rowID"] = self.row_id
        return d


@dataclass
class GroupCount:
    """GroupBy entry (reference pilosa.go GroupCount)."""

    group: list[FieldRow]
    count: int

    def to_dict(self) -> dict:
        return {"group": [g.to_dict() for g in self.group], "count": self.count}


def result_to_json(result: Any) -> Any:
    """Lower any executor result to JSON-encodable data (the HTTP layer's
    QueryResult union, reference internal/public.proto:72-82)."""
    if isinstance(result, (Row, ValCount, RowIdentifiers, GroupCount)):
        return result.to_dict()
    if isinstance(result, Pair):
        return result.to_dict()
    if isinstance(result, list):
        return [result_to_json(r) for r in result]
    if isinstance(result, (bool, int, str)) or result is None:
        return result
    if isinstance(result, np.integer):
        return int(result)
    raise TypeError(f"unencodable result type: {type(result)!r}")
