"""Command-line interface (reference: cmd/ cobra tree + ctl/ subcommands).

    pilosa-tpu server            run a node (reference ctl/server)
    pilosa-tpu import            CSV/value import into a running node
    pilosa-tpu export            CSV export from a running node
    pilosa-tpu check             offline integrity check of fragment files
                                 (reference ctl/check.go:47-133)
    pilosa-tpu inspect           print container stats of fragment files
                                 (reference ctl/inspect.go)
    pilosa-tpu generate-config   emit default config
                                 (reference ctl/generate_config.go)

Config precedence mirrors the reference (cmd/root.go): flags > env
(PILOSA_TPU_*) > config file (JSON or TOML) > defaults.
"""

from __future__ import annotations

# graftlint: disable-file=log-discipline -- CLI subcommands: stdout IS the
# user interface (CSV export, inspect tables, config emission)

import argparse
import json
import os
import sys
import urllib.request

DEFAULT_CONFIG = {
    "data-dir": "~/.pilosa-tpu",
    "bind": "localhost:10101",
    "long-query-time": 0.0,
    # null = auto (80% of the accelerator's bytes_limit on TPU, unlimited
    # accounting on CPU — core/membudget.py); 0 = force unlimited
    # accounting; >0 = explicit cap in bytes
    "hbm-budget-bytes": None,
    "cluster": {"replicas": 1, "coordinator": True, "hosts": []},
    # reference api.go:66-96 importWorkerPoolSize (default 2)
    "import": {"workers": 2, "queue-depth": 16},
    "anti-entropy": {"interval": 600},
    # reference server/config.go:160 MaxWritesPerRequest (0 disables)
    "max-writes-per-request": 5000,
    "metric": {"service": "none", "poll-interval": 60, "diagnostics-sink": ""},
    "tracing": {"enabled": False},
    # crash-durable diagnostics spool under <data-dir>/_blackbox/
    # (obs/blackbox.py); postmortems served at GET /debug/postmortem
    "blackbox": {
        "enabled": True,
        "interval": 5.0,
        "max-segments": 64,
        "max-bytes": 16 << 20,
        "keep-postmortems": 4,
        "history-window": 60.0,
    },
}


def _load_config(path: str | None) -> dict:
    cfg = json.loads(json.dumps(DEFAULT_CONFIG))  # deep copy
    if path:
        with open(path, "rb") as f:
            if path.endswith(".toml"):
                import tomllib

                file_cfg = tomllib.load(f)
            else:
                file_cfg = json.load(f)
        _deep_update(cfg, file_cfg)
    env_map = {
        "PILOSA_TPU_DATA_DIR": ("data-dir",),
        "PILOSA_TPU_BIND": ("bind",),
        "PILOSA_TPU_LONG_QUERY_TIME": ("long-query-time",),
        "PILOSA_TPU_HBM_BUDGET_BYTES": ("hbm-budget-bytes",),
    }
    for env, keys in env_map.items():
        if env in os.environ:
            d = cfg
            for k in keys[:-1]:
                d = d[k]
            d[keys[-1]] = os.environ[env]
    return cfg


def _deep_update(dst: dict, src: dict) -> None:
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            _deep_update(dst[k], v)
        else:
            dst[k] = v


def _init_backend() -> str | None:
    """Initialise the backend the environment asks for; the error text
    when it cannot (no chip, or another process holds it).  There is no
    stand-in: a node that would serve the device path from the CPU
    without being asked to is worse than one that does not start."""
    import jax

    from pilosa_tpu import jaxcache

    jaxcache.configure()
    try:
        jax.devices()
    except RuntimeError as e:
        return str(e)
    return None


def _parse_statsd_host(raw: str) -> tuple[str, int]:
    """(host, port) from a statsd ``host`` config value.  Accepts
    "host:8125", "host" (default port), "[::1]:8125", "[::1]", and a
    bare IPv6 literal "::1" (which a naive rpartition would mangle
    into host ":" port 1)."""
    if raw.startswith("["):
        host, _, rest = raw[1:].partition("]")
        port = rest[1:] if rest.startswith(":") else "8125"
    elif raw.count(":") == 1:
        host, _, port = raw.partition(":")
    else:
        host, port = raw, "8125"
    if not port.isdigit():
        port = "8125"
    return host or "127.0.0.1", int(port)


def cmd_server(args) -> int:
    err = _init_backend()
    if err is not None:
        print(f"error: JAX backend failed to initialise: {err}", file=sys.stderr)
        return 1
    from pilosa_tpu.obs.stats import MemStatsClient, NOP
    from pilosa_tpu.server.node import NodeServer

    cfg = _load_config(args.config)
    data_dir = os.path.expanduser(args.data_dir or cfg["data-dir"])
    bind = args.bind or cfg["bind"]
    host, _, port = bind.rpartition(":")
    host = host or "localhost"

    # HBM budget precedence: flag > env/config > auto-probe at first use
    # (membudget.default_budget).  Explicit 0 on ANY channel forces
    # unlimited accounting; absence means auto.
    from pilosa_tpu.core import membudget

    hbm = args.hbm_budget
    if hbm is None:
        raw = cfg.get("hbm-budget-bytes")
        hbm = int(raw) if raw is not None else None
    if hbm is not None:
        membudget.configure(hbm or None)

    # metric.service selects the backend (reference server.go:397-411):
    # none | expvar/prometheus (in-memory, served at /metrics and
    # /debug/vars) | statsd/datadog (UDP push, reference
    # statsd/statsd.go:48).
    metric_cfg = cfg.get("metric", {})
    service = metric_cfg.get("service", "none")
    if service == "none":
        stats_client = NOP
    elif service in ("statsd", "datadog"):
        from pilosa_tpu.obs.stats import StatsDClient

        mhost, mport = _parse_statsd_host(
            metric_cfg.get("host", "127.0.0.1:8125")
        )
        stats_client = StatsDClient(mhost, mport)
    else:  # expvar / prometheus: in-memory client served over HTTP
        stats_client = MemStatsClient()
    tls_cfg = cfg.get("tls", {})
    node = NodeServer(
        data_dir=data_dir,
        host=host,
        port=int(port),
        replica_n=int(cfg.get("cluster", {}).get("replicas", 1)),
        long_query_time=float(cfg["long-query-time"]),
        stats_client=stats_client,
        metric_poll_interval=float(metric_cfg.get("poll-interval", 10) or 10),
        tls_cert=args.tls_cert or tls_cfg.get("certificate") or None,
        tls_key=args.tls_key or tls_cfg.get("key") or None,
        tls_skip_verify=bool(tls_cfg.get("skip-verify", False)),
        tls_ca_cert=getattr(args, "tls_ca_cert", None)
        or tls_cfg.get("ca-certificate")
        or None,
        import_workers=int(cfg.get("import", {}).get("workers", 2)),
        max_writes_per_request=int(cfg.get("max-writes-per-request", 5000)),
        import_queue_depth=int(cfg.get("import", {}).get("queue-depth", 16)),
        blackbox_enabled=bool(cfg.get("blackbox", {}).get("enabled", True)),
        blackbox_interval=float(cfg.get("blackbox", {}).get("interval", 5.0)),
        blackbox_max_segments=int(
            cfg.get("blackbox", {}).get("max-segments", 64)
        ),
        blackbox_max_bytes=int(
            cfg.get("blackbox", {}).get("max-bytes", 16 << 20)
        ),
        blackbox_keep_postmortems=int(
            cfg.get("blackbox", {}).get("keep-postmortems", 4)
        ),
        blackbox_history_window=float(
            cfg.get("blackbox", {}).get("history-window", 60.0)
        ),
    )
    if node.postmortem is not None:
        pm = node.postmortem
        print(
            f"previous life died dirty: postmortem {pm['id']} "
            f"(crash loop {pm['crashLoop']}) at /debug/postmortem"
        )
    # SIGTERM drains the node and exits 0 — an orderly stop must never
    # read as a crash on the next boot
    node.install_signal_handlers()
    # tracing exporter + sampler (reference tracing config
    # server/config.go:139-145)
    trace_cfg = cfg.get("tracing", {})
    if trace_cfg.get("endpoint"):
        from pilosa_tpu.obs.export import OTLPSpanExporter
        from pilosa_tpu.obs.tracing import ExportingTracer, set_tracer

        set_tracer(
            ExportingTracer(
                OTLPSpanExporter(trace_cfg["endpoint"]),
                sample_rate=float(trace_cfg.get("sampler-param", 1.0)),
            )
        )
    # Periodic diagnostics flushes need somewhere to go (the reference
    # phones home; here a local JSONL sink). Without a sink the
    # /internal/diagnostics route serves snapshots on demand instead.
    diag_sink = metric_cfg.get("diagnostics-sink")
    if diag_sink:
        node.diagnostics.sink_path = os.path.expanduser(diag_sink)
        node.diagnostics.start(float(metric_cfg.get("poll-interval", 60) or 60))
    node.start()
    # periodic replica repair + translate-log replication (reference
    # server.go:494-546 monitorAntiEntropy; 0 disables)
    node.start_anti_entropy(
        float(cfg.get("anti-entropy", {}).get("interval", 600) or 0)
    )
    print(f"pilosa-tpu server listening on {node.uri}, data dir {data_dir}")
    try:
        import threading

        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        node.stop()
    return 0


def _http(args, method: str, path: str, body: bytes | None = None, content_type="application/json"):
    url = f"http://{args.host}{path}"
    req = urllib.request.Request(url, data=body, method=method)
    req.add_header("Content-Type", content_type)
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.read()


def cmd_import(args) -> int:
    """CSV import (reference ctl/import.go:82-378): lines of row,col or
    col,value with --field-type int."""
    rows, cols, values, timestamps = [], [], [], []
    has_ts = False
    for path in args.files:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                parts = line.split(",")
                if args.int_values:
                    cols.append(int(parts[0]))
                    values.append(int(parts[1]))
                else:
                    rows.append(parts[0] if args.row_keys else int(parts[0]))
                    cols.append(parts[1] if args.col_keys else int(parts[1]))
                    if len(parts) > 2:
                        has_ts = True
                        timestamps.append(parts[2])
                    else:
                        timestamps.append(None)
    if args.int_values:
        payload = {"columnIDs": cols, "values": values}
    else:
        payload = {
            ("rowKeys" if args.row_keys else "rowIDs"): rows,
            ("columnKeys" if args.col_keys else "columnIDs"): cols,
        }
        if has_ts:
            payload["timestamps"] = timestamps
    if args.clear:
        payload["clear"] = True
    _http(
        args,
        "POST",
        f"/index/{args.index}/field/{args.field}/import",
        json.dumps(payload).encode(),
    )
    total = len(cols)
    print(f"imported {total} records into {args.index}/{args.field}")
    return 0


def cmd_export(args) -> int:
    data = _http(args, "GET", f"/export?index={args.index}&field={args.field}")
    out = sys.stdout if args.output == "-" else open(args.output, "w")
    out.write(data.decode())
    if out is not sys.stdout:
        out.close()
    return 0


def cmd_check(args) -> int:
    """Offline integrity check of roaring fragment files (reference
    ctl/check.go:47-133)."""
    from pilosa_tpu.storage import roaring

    failed = 0
    for path in args.files:
        try:
            with open(path, "rb") as f:
                positions = roaring.deserialize(f.read())
            print(f"{path}: OK ({len(positions)} bits)")
        except Exception as e:
            print(f"{path}: FAILED: {e}")
            failed += 1
    return 1 if failed else 0


def cmd_inspect(args) -> int:
    """Container statistics of a fragment file (reference ctl/inspect.go)."""
    import numpy as np

    from pilosa_tpu.storage import roaring

    for path in args.files:
        with open(path, "rb") as f:
            data = f.read()
        positions = roaring.deserialize(data)
        keys = positions >> np.uint64(16) if len(positions) else positions
        n_containers = len(np.unique(keys)) if len(positions) else 0
        print(f"{path}:")
        print(f"  bits: {len(positions)}")
        print(f"  containers: {n_containers}")
        if len(positions):
            print(f"  min position: {positions.min()}")
            print(f"  max position: {positions.max()}")
    return 0


def _cluster_hosts(args) -> tuple[list[str], str]:
    """([host:port of every live node], primary's host:port) — backup
    must see EVERY node's fragments and the translation PRIMARY's log
    (a replica's copy can lag by one anti-entropy interval)."""
    try:
        nodes = json.loads(_http(args, "GET", "/internal/nodes"))
    except Exception:
        return [args.host], args.host
    hosts, primary = [], args.host
    for n in nodes:
        uri = n.get("uri", "")
        host = uri.split("://", 1)[-1] if uri else ""
        if not host:
            continue
        hosts.append(host)
        if n.get("isCoordinator"):
            primary = host
    return hosts or [args.host], primary


def cmd_backup(args) -> int:
    """Online backup of a running node/cluster into one tar (reference
    fragment.go:2424-2594's tar fragment format, operator-facing like
    ctl backup): schema.json + translate.json + every fragment as a
    roaring blob at fragments/<index>/<field>/<view>/<shard>.roaring.
    The fragment inventory is the union over EVERY cluster node (each
    node reports only its local fragments) and each blob is fetched
    from a node that holds it; the translation feed comes from the
    primary.  Row/column attributes are not included."""
    import argparse as _argparse
    import io
    import tarfile

    def add(tar, name: str, data: bytes) -> None:
        info = tarfile.TarInfo(name)
        info.size = len(data)
        tar.addfile(info, io.BytesIO(data))

    hosts, primary_host = _cluster_hosts(args)
    schema = _http(args, "GET", "/schema")
    # union inventory; remember one holder per fragment
    holder_of: dict[tuple, str] = {}
    for host in hosts:
        hargs = _argparse.Namespace(host=host)
        inv = json.loads(_http(hargs, "GET", "/internal/fragments"))[
            "fragments"
        ]
        for f in inv:
            if args.index and f["index"] != args.index:
                continue
            holder_of.setdefault(
                (f["index"], f["field"], f["view"], f["shard"]), host
            )
    # full translation feed from the PRIMARY (pull in pages)
    pargs = _argparse.Namespace(host=primary_host)
    entries, offset = [], 0
    while True:
        page = json.loads(
            _http(pargs, "GET", f"/internal/translate/log?offset={offset}")
        )
        entries.extend(page["entries"])
        if page["offset"] == offset:
            break
        offset = page["offset"]
    if args.index:
        # column keys live under the index name; row keys under the
        # same index with a field name — both carry entry[0] == index
        entries = [e for e in entries if e[0] == args.index]
    out = sys.stdout.buffer if args.output == "-" else open(args.output, "wb")
    with tarfile.open(fileobj=out, mode="w|") as tar:
        add(tar, "schema.json", schema)
        add(tar, "translate.json", json.dumps({"entries": entries}).encode())
        for (index, field, view, shard), host in sorted(holder_of.items()):
            blob = _http(
                _argparse.Namespace(host=host),
                "GET",
                f"/internal/fragment/data?index={index}&field={field}"
                f"&view={view}&shard={shard}",
            )
            add(
                tar,
                f"fragments/{index}/{field}/{view}/{shard}.roaring",
                blob,
            )
    if out is not sys.stdout.buffer:
        out.close()
    print(
        f"backed up {len(holder_of)} fragments, {len(entries)} key mappings",
        file=sys.stderr,
    )
    return 0


def cmd_restore(args) -> int:
    """Restore a backup tar into a running node/cluster: apply schema,
    install key translations, then import-roaring every fragment (the
    import path routes each shard to its owners, so restoring into a
    different cluster shape re-places the data)."""
    import tarfile

    src = sys.stdin.buffer if args.file == "-" else open(args.file, "rb")
    n_frags = 0
    with tarfile.open(fileobj=src, mode="r|*") as tar:
        for member in tar:
            f = tar.extractfile(member)
            if f is None:
                continue
            data = f.read()
            if member.name == "schema.json":
                # /schema applies locally (the resize path uses it
                # per-node), so install it on EVERY node before any
                # fragment import forwards to a replica
                import argparse as _argparse

                hosts, _ = _cluster_hosts(args)
                for host in hosts:
                    _http(
                        _argparse.Namespace(host=host),
                        "POST",
                        "/schema",
                        data,
                    )
            elif member.name == "translate.json":
                _http(
                    args, "POST", "/internal/translate/restore", data
                )
            elif member.name.startswith("fragments/"):
                _, index, field, view, fname = member.name.split("/")
                shard = int(fname.removesuffix(".roaring"))
                _http(
                    args,
                    "POST",
                    f"/index/{index}/field/{field}/import-roaring/{shard}"
                    f"?view={view}",
                    data,
                    content_type="application/octet-stream",
                )
                n_frags += 1
    if src is not sys.stdin.buffer:
        src.close()
    print(f"restored {n_frags} fragments", file=sys.stderr)
    return 0


def cmd_generate_config(args) -> int:
    print(json.dumps(DEFAULT_CONFIG, indent=2))
    return 0


def cmd_config(args) -> int:
    """Print the effective configuration after file + env merging
    (reference `pilosa config`, ctl/config.go)."""
    print(json.dumps(_load_config(args.config), indent=2))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="pilosa-tpu", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    ps = sub.add_parser("server", help="run a pilosa-tpu node")
    ps.add_argument("-d", "--data-dir", default=None)
    ps.add_argument("-b", "--bind", default=None)
    ps.add_argument("-c", "--config", default=None)
    ps.add_argument(
        "--hbm-budget",
        type=int,
        default=None,
        help="HBM budget in bytes for device-resident fragment/stack "
        "copies (default: 80%% of the accelerator's memory limit)",
    )
    ps.add_argument("--tls-cert", default=None, help="TLS certificate path (enables HTTPS)")
    ps.add_argument("--tls-key", default=None, help="TLS private key path")
    ps.add_argument(
        "--tls-ca-cert",
        default=None,
        help="CA bundle for verifying intra-cluster certs (private CA)",
    )
    ps.set_defaults(fn=cmd_server)

    for name, fn in [("import", cmd_import)]:
        pi = sub.add_parser(name, help="bulk import CSV")
        pi.add_argument("--host", default="localhost:10101")
        pi.add_argument("-i", "--index", required=True)
        pi.add_argument("-f", "--field", required=True)
        pi.add_argument("--int-values", action="store_true", help="col,value CSV for int fields")
        pi.add_argument("--row-keys", action="store_true")
        pi.add_argument("--col-keys", action="store_true")
        pi.add_argument("--clear", action="store_true")
        pi.add_argument("files", nargs="+")
        pi.set_defaults(fn=fn)

    pe = sub.add_parser("export", help="export a field as CSV")
    pe.add_argument("--host", default="localhost:10101")
    pe.add_argument("-i", "--index", required=True)
    pe.add_argument("-f", "--field", required=True)
    pe.add_argument("-o", "--output", default="-")
    pe.set_defaults(fn=cmd_export)

    pb = sub.add_parser("backup", help="backup a running cluster to a tar")
    pb.add_argument("--host", default="localhost:10101")
    pb.add_argument("-o", "--output", default="-")
    pb.add_argument("-i", "--index", default=None, help="only this index")
    pb.set_defaults(fn=cmd_backup)

    pr = sub.add_parser("restore", help="restore a backup tar into a cluster")
    pr.add_argument("--host", default="localhost:10101")
    pr.add_argument("file", help="backup tar path, or - for stdin")
    pr.set_defaults(fn=cmd_restore)

    pc = sub.add_parser("check", help="verify fragment files")
    pc.add_argument("files", nargs="+")
    pc.set_defaults(fn=cmd_check)

    pn = sub.add_parser("inspect", help="inspect fragment files")
    pn.add_argument("files", nargs="+")
    pn.set_defaults(fn=cmd_inspect)

    pg = sub.add_parser("generate-config", help="print default config")
    pg.set_defaults(fn=cmd_generate_config)

    pcfg = sub.add_parser(
        "config", help="print the effective config (file + env merged)"
    )
    pcfg.add_argument("-c", "--config", default=None)
    pcfg.set_defaults(fn=cmd_config)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
