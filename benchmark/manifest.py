"""``BENCHMARK.json`` as the harness reads it, and the result line.

The last line of a run is built by :func:`result_line` alone: it walks the
metrics the manifest gives this cell for this kind of run (``--trace 0``:
end-to-end, ``--trace 1``: per-layer) and asks each for its number.  A
listed metric that yields no number ends the run before anything is
printed; a number that is not listed is never printed.
:func:`validate_line` is the same check from the reader's side, and the
tests run it over every cell and both kinds of run.
"""

from __future__ import annotations

import json
import math
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device", "breakdown", "compared")


class ManifestError(Exception):
    pass


def load(path: str | None = None) -> dict:
    with open(path or os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _named(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"no {what} {name!r} in BENCHMARK.json (has: {', '.join(e['name'] for e in entries)})")


def cell(manifest: dict, name: str) -> dict:
    return _named(manifest["workloads"], name, "workload")


def config_entry(manifest: dict, name: str) -> dict:
    return _named(manifest["configs"], name, "configuration")


def read_json(relpath: str) -> dict:
    with open(os.path.join(REPO, relpath)) as f:
        return json.load(f)


def metrics_for(manifest: dict, workload: str, traced: bool) -> list[dict]:
    """The metrics this cell reports in this kind of run, in the
    manifest's order.  A per-layer metric without ``workloads`` belongs to
    every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if not traced:
        return e2e
    have = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in have)]


def result_line(manifest: dict, workload: str, traced: bool, read_metric, *, correct: bool,
                attempted: int, failed: int, device: dict, compared: dict,
                breakdown: dict | None = None) -> str:
    """The one place a result line is made.  ``read_metric(metric)`` gives
    the number of one manifest entry or raises."""
    metrics = {}
    for m in metrics_for(manifest, workload, traced):
        value = read_metric(m)
        if isinstance(value, bool) or not isinstance(value, (int, float)) or not math.isfinite(value):
            raise ManifestError(f"metric {m['name']} of {workload} read {value!r}, not a number")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": device}
    if traced and breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared  # each number compared beside its limit; comes last
    problems = validate_line(manifest, workload, traced, line)
    if problems:
        raise ManifestError("result line refused: " + "; ".join(problems))
    return json.dumps(line)


def validate_line(manifest: dict, workload: str, traced: bool, line: dict) -> list[str]:
    """What is wrong with a result line, as the contract reads it."""
    bad = []
    extra = set(line) - set(LINE_KEYS)
    if extra:
        bad.append(f"keys the contract does not have: {sorted(extra)}")
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        if k not in line:
            bad.append(f"lacks {k}")
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not true or false")
    want = {m["name"]: m for m in metrics_for(manifest, workload, traced)}
    got = line["metrics"]
    if set(got) != set(want):
        bad.append(f"metrics {sorted(got)} are not the manifest's {sorted(want)}")
    for name, m in got.items():
        if not NAME.match(name):
            bad.append(f"metric name {name!r}")
        if set(m) != {"value", "unit"}:
            bad.append(f"{name}: keys {sorted(m)}")
            continue
        if isinstance(m["value"], bool) or not isinstance(m["value"], (int, float)) \
                or not math.isfinite(m["value"]):
            bad.append(f"{name}: value {m['value']!r}")
        if not UNIT.match(str(m["unit"])) or (name in want and m["unit"] != want[name]["unit"]):
            bad.append(f"{name}: unit {m['unit']!r}")
    dev = line["device"]
    need = ["platform", "kind", "count", "memory_peak_bytes"] + (["busy_s", "window_s"] if traced else [])
    for k in need:
        if k not in dev:
            bad.append(f"device lacks {k}")
    if traced and not bad:
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            bad.append(f"busy_s {dev['busy_s']} is not above 0 and at most window_s {dev['window_s']}")
    if "breakdown" in line:
        for k, rows in line["breakdown"].items():
            if k not in ("device_ops", "idle_gaps") or len(rows) > 10:
                bad.append(f"breakdown.{k}")
    return bad
