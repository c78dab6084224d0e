"""Star-Trace demo: the reference's getting-started workload end-to-end.

Mirrors the Pilosa tutorial dataset (BASELINE config 1): an index of
GitHub repositories with a `stargazer` time field (user x repo stars with
timestamps) and a `language` mutex field, queried with the tutorial's
PQL shapes:

    Row(stargazer=14)                       repos starred by user 14
    Count(Intersect(Row(...), Row(...)))    repos two users both starred
    TopN(language, n=5)                     most common languages
    TopN(stargazer, n=5)                    most active stargazers
    Row(stargazer=14, from=..., to=...)     stars in a time window
    GroupBy(Rows(language), Rows(stargazer), limit=8)

Data is synthetic (zipf-ish stars over users/repos/languages) so the demo
runs offline. Usage:

    python examples/startrace.py [--host HOST:PORT]

Without --host it boots an in-process node, so it doubles as an
end-to-end smoke test of the full server stack.
"""

from __future__ import annotations

import argparse
import json
import sys
import os

# runnable from a checkout without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time
import urllib.request

import numpy as np

N_USERS = 2000
N_REPOS = 5000
N_LANGS = 12
N_STARS = 60_000


def synth(rng):
    users = rng.zipf(1.5, size=N_STARS).clip(max=N_USERS) - 1
    repos = rng.zipf(1.3, size=N_STARS).clip(max=N_REPOS) - 1
    days = rng.integers(0, 365, size=N_STARS)
    langs = rng.integers(0, N_LANGS, size=N_REPOS)
    return users.astype(int), repos.astype(int), days, langs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default=None, help="server host:port (default: in-process)")
    args = ap.parse_args()

    node = None
    if args.host:
        base = f"http://{args.host}"
    else:
        from pilosa_tpu.server.node import NodeServer

        node = NodeServer()
        node.start()
        base = node.uri

    def req(method, path, body=None):
        r = urllib.request.Request(
            base + path,
            data=body.encode() if isinstance(body, str) else body,
            method=method,
        )
        with urllib.request.urlopen(r, timeout=120) as resp:
            return json.loads(resp.read() or b"{}")

    def query(pql):
        return req("POST", "/index/repository/query", pql)["results"]

    print(f"server: {base}")
    req("POST", "/index/repository", "{}")
    req(
        "POST",
        "/index/repository/field/stargazer",
        json.dumps({"options": {"type": "time", "timeQuantum": "YMD"}}),
    )
    req("POST", "/index/repository/field/language", json.dumps({"options": {"type": "mutex"}}))

    rng = np.random.default_rng(42)
    users, repos, days, langs = synth(rng)

    t0 = time.perf_counter()
    batch = []
    for u, r, d in zip(users, repos, days):
        ts = f"2017-{1 + d // 31:02d}-{1 + d % 28:02d}T00:00"
        batch.append(f"Set({r}, stargazer={u}, {ts})")
    for r, l in enumerate(langs):
        batch.append(f"Set({r}, language={l})")
    CHUNK = 4000
    for i in range(0, len(batch), CHUNK):
        query(" ".join(batch[i : i + CHUNK]))
    ingest_s = time.perf_counter() - t0
    print(f"ingested {N_STARS} stars + {N_REPOS} languages in {ingest_s:.1f}s")

    t0 = time.perf_counter()
    starred_by_14 = query("Row(stargazer=14)")[0]["columns"]
    both = query("Count(Intersect(Row(stargazer=14), Row(stargazer=15)))")[0]
    top_langs = query("TopN(language, n=5)")[0]
    top_stars = query("TopN(stargazer, n=5)")[0]
    window = query(
        "Row(stargazer=14, from=2017-01-01T00:00, to=2017-03-01T00:00)"
    )[0]["columns"]
    groups = query("GroupBy(Rows(language), Rows(stargazer), limit=8)")[0]
    query_s = time.perf_counter() - t0

    print(f"user 14 starred {len(starred_by_14)} repos; 14∩15 = {both}")
    print("top languages:", [(p["id"], p["count"]) for p in top_langs])
    print("top stargazers:", [(p["id"], p["count"]) for p in top_stars])
    print(f"user 14 stars in Jan-Feb window: {len(window)}")
    print(f"groupby sample: {groups[:3]}")
    print(f"6 tutorial queries in {query_s * 1e3:.0f}ms")

    ok = (
        len(starred_by_14) > 0
        and both >= 0
        and len(top_langs) == 5
        and sorted(
            (p["count"] for p in top_langs), reverse=True
        ) == [p["count"] for p in top_langs]
        and len(window) <= len(starred_by_14)
    )
    if node is not None:
        node.stop()
    print("OK" if ok else "MISMATCH")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
