"""Programmatic API surface (reference: api.go, 1414 LoC).

Every HTTP route lands here. Methods are **state-gated** exactly like the
reference (api.go:100-124 validAPIMethods + apimethod_string.go): during
STARTING only status-ish methods work; during RESIZING only fragment
transfer and abort. A single node sits in NORMAL.
"""

from __future__ import annotations

import io
import json
import logging
import random
import threading
import time
from typing import Any

import numpy as np

from pilosa_tpu import __version__, deadline
from pilosa_tpu.cluster.client import ClientError
from pilosa_tpu.obs import devledger
from pilosa_tpu.obs import events as ev
from pilosa_tpu.obs import qprofile, slo, tracing
from pilosa_tpu.server import qos as qos_mod
from pilosa_tpu.testing import faults
from pilosa_tpu.core.field import FieldOptions
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.core import timequantum
from pilosa_tpu.core.view import VIEW_STANDARD
from pilosa_tpu.exec.executor import ExecuteError, Executor
from pilosa_tpu.exec.rescache import Served
from pilosa_tpu.exec.result import result_to_json
from pilosa_tpu.storage import roaring
from pilosa_tpu.storage.disk import HolderStore

logger = logging.getLogger(__name__)

# Cluster states (reference cluster.go:46-51).
STATE_STARTING = "STARTING"
STATE_NORMAL = "NORMAL"
STATE_DEGRADED = "DEGRADED"
STATE_RESIZING = "RESIZING"

# Methods valid in non-NORMAL states (reference api.go:100-124).
_STARTING_METHODS = {
    "Status", "Info", "Version", "Schema", "ClusterMessage", "Hosts",
}
_RESIZING_METHODS = {
    "Status", "Info", "Version", "ClusterMessage", "Hosts",
    "FragmentData", "ResizeAbort",
}


def encode_json(obj) -> bytes:
    """A JSON response's body as the listener sends it (``http._send_json``)."""
    return (json.dumps(obj) + "\n").encode()


def _as_dict(results) -> dict:
    return {"results": result_to_json(results)}


def _as_body(results) -> "dict | bytes":
    """The response of a ``rescache.Served`` hit as its encoded body, built
    once an entry's result and kept with it; any other as the dict."""
    if not isinstance(results, Served):
        return _as_dict(results)
    body = results.body
    if body is None:
        body = encode_json(_as_dict(results))
        results.remember(body)
    return body


class ApiError(Exception):
    def __init__(self, msg: str, code: int = 400):
        super().__init__(msg)
        self.code = code


class NotFoundError(ApiError):
    def __init__(self, msg: str):
        super().__init__(msg, 404)


class ConflictError(ApiError):
    def __init__(self, msg: str):
        super().__init__(msg, 409)


class API:
    """reference api.go:74 NewAPI."""

    def __init__(
        self,
        holder: Holder | None = None,
        store: HolderStore | None = None,
        cluster=None,
        client=None,
        broadcaster=None,
        import_workers: int = 2,
        import_queue_depth: int = 16,
        ingest_staging_buffers: int = 4,
        ingest_upload_slots: int = 2,
        max_writes_per_request: int | None = None,
        batch_window: float = 0.002,
        batch_max_size: int = 64,
        rescache_entries: int = 512,
        rescache_promote_hits: int = 3,
        rescache_demote_deltas: int = 64,
        planner_enabled: bool = True,
        qos_enabled: bool = True,
        qos_weights: dict | None = None,
        qos_down_factor: float = 8.0,
        qos_stage_hold: float = 2.0,
        qos_relax_hold: float = 5.0,
        qos_tick_interval: float = 0.25,
        qos_retry_after: float = 1.0,
        qos_aggressor_share: float = 0.5,
    ):
        self.holder = holder or Holder()
        self.store = store
        self.cluster = cluster
        self.client = client
        self.broadcaster = broadcaster
        translator = store.translator if store is not None else None
        self.executor = Executor(
            self.holder,
            translator=translator,
            max_writes_per_request=max_writes_per_request,
            rescache_entries=rescache_entries,
            rescache_promote_hits=rescache_promote_hits,
            rescache_demote_deltas=rescache_demote_deltas,
            planner_enabled=planner_enabled,
        )
        # Cluster-aware execution path (reference executor.go mapReduce);
        # collapses to the local executor on a single node.
        self.dist = None
        if cluster is not None and client is not None:
            from pilosa_tpu.cluster.dist import DistributedExecutor

            self.dist = DistributedExecutor(
                self.holder, cluster, client, translator=translator,
                local_executor=self.executor,
            )
        self._lock = threading.RLock()
        self._state = STATE_NORMAL
        # Slow-query ring (reference long-query-time log line, upgraded
        # to full profiles at /debug/slow-queries); the server sets the
        # threshold from config.
        self.slow_queries = qprofile.SlowQueryLog()
        # Diagnostics collector; NodeServer installs one (reference
        # server.go diagnostics wiring).
        self.diagnostics = None
        # Flight recorder + incident engine; NodeServer installs one
        # (obs/flightrec.py) — None means /debug/incidents serves empty.
        self.flightrec = None
        # Ring-buffer metrics history + trend detectors; NodeServer
        # installs one (obs/history.py) — None 404s /debug/history.
        self.history = None
        # Crash-durable black box; NodeServer installs one when it has
        # a data dir (obs/blackbox.py) — None 404s /debug/postmortem.
        self.blackbox = None
        # Bounded import worker pool: concurrency limit + backpressure
        # (reference api.go:66-96 importWorkerPoolSize default 2,
        # importWorker :313-348; both knobs configurable like the
        # reference's server config).
        from pilosa_tpu.server.importpool import ImportPool

        self.import_pool = ImportPool(
            workers=import_workers, depth=import_queue_depth,
            jobs=self.holder.jobs, stats=self.holder.stats,
        )
        # Staged ingest pipeline over the pool (pilosa_tpu/ingest/):
        # zero-copy decode into staging buffers, sharded coalescing
        # drains, double-buffered host->device uploads.
        from pilosa_tpu.ingest import IngestPipeline

        self.ingest = IngestPipeline(
            self.import_pool,
            stats=self.holder.stats,
            staging_buffers=ingest_staging_buffers,
            upload_slots=ingest_upload_slots,
        )
        # Ingest applies invalidate (or delta-maintain) semantic-cache
        # entries inside the same group-commit — version-precise, never
        # a global flush (exec/rescache.py).
        self.ingest.on_apply = lambda frag: self.executor.rescache.note_write(
            frag.index, frag.field
        )
        # Continuous-batching serving plane (server/batcher.py):
        # concurrent read-only queries coalesce into micro-batched
        # executor dispatches.  ``batch_window<=0`` or ``batch_max_size
        # <=1`` disables it — every query takes the direct path.  On a
        # clustered node the plane wraps the DISTRIBUTED executor, whose
        # execute/execute_batch collapse to the local executor for
        # single-node clusters and dispatch mesh-complete flights as one
        # sharded launch (cluster/dist.py execute_batch).
        from pilosa_tpu.server.batcher import QueryBatcher
        from pilosa_tpu.server.qos import QosGovernor

        self.batcher = None
        self.prefetcher = None
        self.qos = None
        if batch_window > 0 and batch_max_size > 1:
            # Cost-governed multi-tenant admission (server/qos.py):
            # weighted-fair queues debited by measured device-ms, plus
            # the deprioritize/degrade/shed pressure ladder.  The
            # control-loop taps are callables so the flight recorder
            # (installed later by NodeServer) is picked up live.
            self.qos = QosGovernor(
                stats=self.holder.stats,
                weights=qos_weights,
                enabled=qos_enabled,
                down_factor=qos_down_factor,
                stage_hold=qos_stage_hold,
                relax_hold=qos_relax_hold,
                tick_interval=qos_tick_interval,
                retry_after=qos_retry_after,
                aggressor_share=qos_aggressor_share,
                slo_fn=lambda: self.holder.slo,
                ledger_fn=devledger.tenant_totals,
                journal_fn=lambda: self.holder.events,
                incident_fn=lambda trig: (
                    self.flightrec.capture_incident(trig)
                    if self.flightrec is not None
                    else None
                ),
            )
            # Predictive residency prefetch (server/prefetch.py): the
            # batcher's admission queue resolves each flight's cold
            # fragments onto the ingest uploader's low-priority lane, so
            # H2D staging overlaps compute under an oversubscribed HBM
            # budget.  No-op while the budget is uncapped.
            if self.ingest.uploader is not None:
                from pilosa_tpu.server.prefetch import FlightPrefetcher

                self.prefetcher = FlightPrefetcher(
                    self.holder, self.ingest.uploader, self.executor
                )
            self.batcher = QueryBatcher(
                self.dist if self.dist is not None else self.executor,
                stats=self.holder.stats,
                window=batch_window,
                max_batch=batch_max_size,
                prefetcher=self.prefetcher,
                qos=self.qos,
            )
        # Online-migration state (cluster/migration.py): source-side
        # session registry (snapshot cut + delta tap per in-flight
        # fragment transfer) and the target-side held pulls awaiting the
        # post-flip finalize drain.
        from pilosa_tpu.cluster.migration import MigrationRegistry

        self.migrations = MigrationRegistry(self._node_id())
        self._migrate_pulls: dict[tuple, dict] = {}
        self._migrate_lock = threading.Lock()
        # Coordinator-side resume state: in-process mirror of the
        # on-disk resize journal, so storeless clusters can resume an
        # interrupted resize too (cluster/resize.py).
        self._resize_journal: dict | None = None

    @property
    def state(self) -> str:
        if self.cluster is not None and hasattr(self.cluster, "state"):
            return self.cluster.state
        return self._state

    @state.setter
    def state(self, value: str) -> None:
        if self.cluster is not None and hasattr(self.cluster, "set_state"):
            self.cluster.set_state(value)
        else:
            self._state = value

    def _broadcast(self, msg: dict) -> None:
        """Best-effort control-plane fan-out: a peer that misses a schema
        message re-converges via the schema sync pass of anti-entropy
        (the reference re-exchanges full NodeStatus incl. schema on every
        gossip push/pull, gossip.go:321-357). Raising here instead would
        leave the already-committed local mutation un-broadcast forever,
        since a client retry hits ConflictError before re-broadcasting."""
        if self.broadcaster is None:
            return
        try:
            self.broadcaster.send_sync(msg)
        except Exception as e:
            logger.warning("broadcast %s failed: %s", msg.get("type"), e)

    # -- state gating (reference api.go:100-124) ---------------------------

    def _validate(self, method: str) -> None:
        if self.state == STATE_NORMAL or self.state == STATE_DEGRADED:
            return
        allowed = (
            _STARTING_METHODS if self.state == STATE_STARTING else _RESIZING_METHODS
        )
        if method not in allowed:
            raise ApiError(
                f"api method {method} not allowed in state {self.state}", 503
            )

    # -- queries ------------------------------------------------------------

    def query(
        self,
        index: str,
        pql: str,
        shards: list[int] | None = None,
        remote: bool = False,
        profile: bool = False,
    ) -> dict:
        """reference api.go:134 Query. ``remote=True`` marks a mapped
        sub-query from another node's coordinator (reference Remote:true
        QueryRequest): keys arrive pre-translated, results return in wire
        encoding for the caller's reduce step.  ``profile=True`` returns
        the per-query call tree (spans, kernel dispatches, cache hits,
        remote sub-profiles) under ``"profile"`` alongside the results;
        a profile is also collected — without being returned — whenever
        the slow-query log is armed, so threshold breaches capture a
        full tree."""
        return self._query(index, pql, shards, remote, profile, _as_dict)

    def query_encoded(
        self,
        index: str,
        pql: str,
        shards: list[int] | None = None,
        remote: bool = False,
        profile: bool = False,
    ) -> "dict | bytes":
        """:meth:`query` for the listener, which sends bytes: where the
        result cache answered the request with an object nobody will
        write to (``rescache.Served``), the response is the encoded body
        itself — the bytes the entry's first hit was sent as, byte for
        byte what :func:`encode_json` makes of :meth:`query`'s dict.
        That first hit builds them here, as ever, and leaves them with
        the entry.  Every other request gets :meth:`query`'s dict."""
        return self._query(index, pql, shards, remote, profile, _as_body)

    def _query(self, index, pql, shards, remote, profile, render):
        self._validate("Query")
        # Fail fast if the budget is already spent (e.g. a forwarded
        # sub-query whose header arrived expired) — DeadlineExceeded is
        # deliberately outside the ApiError catch below so it reaches
        # the transport layer's 504 mapping.
        deadline.check(f"query on {index!r}")
        from pilosa_tpu.pql import ParseError

        if remote:
            # node↔node fan-out sub-query: the user-facing request is
            # already on the coordinator's budget — don't double-count
            # it against a read class on this node.
            slo.note_class(slo.OP_INTERNAL)
        prof = None
        if profile or self.slow_queries.enabled:
            node_id = getattr(self.cluster, "node_id", "") if self.cluster else ""
            prof = qprofile.QueryProfile(index, pql, node_id=node_id)
        t0 = time.perf_counter()
        err = None
        try:
            with qprofile.activate(prof):
                try:
                    if remote and self.dist is not None:
                        from pilosa_tpu.cluster.wire import encode_results

                        results = self.dist.execute_remote(index, pql, shards)
                        resp = {"wireResults": encode_results(results)}
                    else:
                        results = self._execute_query(index, pql, shards)
                        # Degraded tier is EXPLICIT: a last-known
                        # answer served under QoS pressure stage 2 is
                        # marked in the envelope (server/qos.py sets
                        # the request-scoped note in batcher.submit)
                        if qos_mod.take_degraded():
                            resp = _as_dict(results)
                            resp["degraded"] = True
                        else:
                            resp = render(results)
                except (ExecuteError, ParseError, ValueError, TypeError) as e:
                    err = str(e)
                    raise ApiError(str(e))
        except BaseException as e:
            if err is None:
                err = repr(e)  # timeouts etc. still land in the slow log
            raise
        finally:
            if prof is not None:
                prof.finish(time.perf_counter() - t0, error=err)
                self.slow_queries.observe(prof)
        if prof is not None and profile:
            # a dict: no request that collects a profile is Served
            resp["profile"] = prof.to_dict()
        return resp

    def _execute_query(self, index: str, pql_text: str, shards):
        """Route one local query: read-only queries ride the
        continuous-batching plane (``batcher.submit`` parks this handler
        thread until its micro-batch lands) when they resolve entirely
        on this node OR onto the local serving mesh — a mesh-complete
        flight dispatches as ONE sharded launch (cluster/dist.py
        execute_batch) instead of N HTTP subrequests.  Writes and
        fan-outs with off-mesh owners keep the direct path — writes for
        strict in-order semantics, off-mesh fan-outs because the
        distributed executor batches per-hop itself (ROADMAP item 4)."""
        from pilosa_tpu import pql

        if isinstance(pql_text, str):
            with tracing.start_span("api.parse") as sp:
                q, hit = pql.parse_noting_hit(pql_text)
                sp.set_tag("cacheHit", hit)
        else:
            q = pql_text
        # SLO op class rides a contextvar to the HTTP layer's recording
        # point (this thread handles the whole request).
        op_class = slo.classify_query(q)
        slo.note_class(op_class)
        # Device cost ledger principal: every launch this query causes —
        # inline, batched (the flight snapshots it at submit), or
        # mesh-dispatched — books under (tenant, index, op_class).
        with devledger.principal_scope(index, op_class):
            batcher = self.batcher
            dist = self.dist
            if batcher is not None and batcher.accepts(q):
                if (
                    dist is None
                    or dist._single
                    or dist.mesh_complete(index, q, shards)
                ):
                    return batcher.submit(index, q, shards=shards)
            if dist is not None:
                return dist.execute(index, q, shards=shards)
            return self.executor.execute(index, q, shards=shards)

    # -- schema CRUD (reference api.go:161-495) -----------------------------

    def schema(self) -> dict:
        self._validate("Schema")
        return {"indexes": self.holder.schema()}

    def apply_schema(self, schema: dict) -> None:
        self._validate("ApplySchema")
        self.holder.apply_schema(schema.get("indexes", []))
        self._sync()

    def create_index(
        self, name: str, options: dict | None = None, broadcast: bool = True
    ) -> dict:
        self._validate("CreateIndex")
        return self._create_index(name, options, broadcast)

    def _create_index(
        self, name: str, options: dict | None = None, broadcast: bool = True
    ) -> dict:
        options = options or {}
        with self._lock:
            if self.holder.index(name) is not None:
                raise ConflictError("index already exists")
            try:
                idx = self.holder.create_index(
                    name,
                    keys=options.get("keys", False),
                    track_existence=options.get("trackExistence", True),
                )
            except ValueError as e:
                raise ApiError(str(e))
        self._sync()
        if broadcast:
            from pilosa_tpu.cluster import broadcast as bc

            self._broadcast(
                {"type": bc.MSG_CREATE_INDEX, "index": name, "options": options}
            )
        return idx.to_dict()

    def delete_index(self, name: str, broadcast: bool = True) -> None:
        self._validate("DeleteIndex")
        self._delete_index(name, broadcast)

    def _delete_index(self, name: str, broadcast: bool = True) -> None:
        if not self.holder.delete_index(name):
            raise NotFoundError("index not found")
        if self.store is not None:
            self.store.delete_index_dir(name)
        if broadcast:
            from pilosa_tpu.cluster import broadcast as bc

            self._broadcast({"type": bc.MSG_DELETE_INDEX, "index": name})

    def index_info(self, name: str) -> dict:
        self._validate("Index")
        idx = self.holder.index(name)
        if idx is None:
            raise NotFoundError("index not found")
        return idx.to_dict()

    def create_field(
        self,
        index: str,
        field: str,
        options: dict | None = None,
        broadcast: bool = True,
    ) -> dict:
        self._validate("CreateField")
        return self._create_field(index, field, options, broadcast)

    def _create_field(
        self,
        index: str,
        field: str,
        options: dict | None = None,
        broadcast: bool = True,
    ) -> dict:
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError("index not found")
        if idx.field(field) is not None:
            raise ConflictError("field already exists")
        try:
            f = idx.create_field(field, FieldOptions.from_dict(options or {}))
        except ValueError as e:
            raise ApiError(str(e))
        self._sync()
        if broadcast:
            from pilosa_tpu.cluster import broadcast as bc

            self._broadcast(
                {
                    "type": bc.MSG_CREATE_FIELD,
                    "index": index,
                    "field": field,
                    "options": options or {},
                }
            )
        return f.to_dict()

    def delete_field(self, index: str, field: str, broadcast: bool = True) -> None:
        self._validate("DeleteField")
        self._delete_field(index, field, broadcast)

    def _delete_field(self, index: str, field: str, broadcast: bool = True) -> None:
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError("index not found")
        if not idx.delete_field(field):
            raise NotFoundError("field not found")
        if self.store is not None:
            self.store.delete_field_dir(index, field)
        if broadcast:
            from pilosa_tpu.cluster import broadcast as bc

            self._broadcast(
                {"type": bc.MSG_DELETE_FIELD, "index": index, "field": field}
            )

    def field_info(self, index: str, field: str) -> dict:
        self._validate("Field")
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError("field not found")
        return f.to_dict()

    # -- imports (reference api.go:919-1112 Import/ImportValue,
    #    :367-427 ImportRoaring) --------------------------------------------

    def import_bits(self, index: str, field: str, req: dict) -> None:
        """JSON bulk import: rowIDs/rowKeys + columnIDs/columnKeys
        (+ timestamps), or columnIDs/columnKeys + values for int fields.

        In cluster mode the receiving node acts as import coordinator
        (reference api.go:919-1112): it translates keys once, splits the
        batch by shard, applies the locally-owned slice, and forwards each
        remaining slice to every replica owning its shard (api.go:964-995),
        marked ``remote`` so receivers do not re-forward."""
        self._validate("Import")
        deadline.check(f"import into {index!r}/{field!r}")
        idx = self.holder.index(index)
        if idx is None:
            raise NotFoundError("index not found")
        f = idx.field(field)
        if f is None:
            raise NotFoundError("field not found")
        translator = self.executor.translator

        cols = req.get("columnIDs")
        if cols is None:
            keys = req.get("columnKeys")
            if keys is None:
                raise ApiError("columnIDs or columnKeys required")
            if not idx.keys:
                raise ApiError("columnKeys given but index does not use keys")
            cols = translator.translate_keys(index, "", keys)
        cols = np.asarray(cols, dtype=np.uint64)

        if not req.get("remote") and self._route_import(index, f, req, cols):
            return
        # The local apply rides the staged ingest pipeline: per-shard
        # segments are submitted to the bounded worker pool (reference
        # api.go:313-348 backpressure semantics) before any is awaited,
        # so distinct fragments drain concurrently while applied
        # fragments upload to the device in the background.  One
        # import-drain record spans the whole request.
        with self.import_pool.drain_scope():
            self._apply_import(idx, f, index, field, req, cols)

    def _apply_import(self, idx, f, index: str, field: str, req: dict, cols) -> None:
        translator = self.executor.translator
        if "values" in req:
            if not f.is_bsi():
                raise ApiError(f"field {field!r} is not an int field")
            values = np.asarray(req["values"], dtype=np.int64)
            if len(values) != len(cols):
                raise ApiError("columns/values length mismatch")
            lo, hi = int(values.min()) if len(values) else 0, int(values.max()) if len(values) else 0
            if len(values) and (lo < f.options.min or hi > f.options.max):
                raise ApiError("value out of field range")
            f.import_values(
                cols, values, clear=req.get("clear", False),
                pipeline=self.ingest,
            )
        else:
            rows = req.get("rowIDs")
            if rows is None:
                keys = req.get("rowKeys")
                if keys is None:
                    raise ApiError("rowIDs or rowKeys required")
                if not f.keys:
                    raise ApiError("rowKeys given but field does not use keys")
                rows = translator.translate_keys(index, field, keys)
            if len(rows) != len(cols):
                raise ApiError("rows/columns length mismatch")
            timestamps = req.get("timestamps")
            ts = None
            if timestamps is not None:
                ts = [
                    timequantum.parse_time(t) if t else None for t in timestamps
                ]
            f.import_bits(
                np.asarray(rows, dtype=np.uint64),
                cols,
                timestamps=ts,
                clear=req.get("clear", False),
                pipeline=self.ingest,
                segments=req.get("_segments"),
            )
        ef = idx.existence_field()
        if ef is not None and not req.get("clear", False):
            ef.import_bits(
                np.zeros(len(cols), dtype=np.uint64), cols,
                pipeline=self.ingest,
            )

    def _route_import(self, index: str, f, req: dict, cols: np.ndarray) -> bool:
        """Cluster import routing (reference api.go:964-995). Returns True
        when the batch was split and dispatched shard-wise to owning
        nodes; False when the caller should apply it wholly locally."""
        if (
            self.cluster is None
            or self.client is None
            or len(self.cluster.nodes) <= 1
        ):
            return False
        translator = self.executor.translator
        values = req.get("values")
        rows = None
        if values is None:
            rows = req.get("rowIDs")
            if rows is None:
                keys = req.get("rowKeys")
                if keys is None:
                    raise ApiError("rowIDs or rowKeys required")
                if not f.keys:
                    raise ApiError("rowKeys given but field does not use keys")
                rows = translator.translate_keys(index, f.name, keys)
            rows = np.asarray(rows, dtype=np.uint64)
            if len(rows) != len(cols):
                raise ApiError("rows/columns length mismatch")
        else:
            values = np.asarray(values, dtype=np.int64)
            if len(values) != len(cols):
                raise ApiError("columns/values length mismatch")
        timestamps = req.get("timestamps")
        width = f.n_words * 32
        shards = cols // np.uint64(width)
        node_masks: dict[str, np.ndarray] = {}
        node_uri: dict[str, str] = {}
        for s in np.unique(shards):
            m = shards == s
            for node in self.cluster.shard_nodes(index, int(s)):
                node_uri[node.id] = node.uri
                node_masks[node.id] = (
                    m if node.id not in node_masks else (node_masks[node.id] | m)
                )
        # Dispatch every node's slice before reporting errors, so one dead
        # replica can't leave later nodes' slices silently undelivered.
        errors: list[str] = []
        for node_id, mask in node_masks.items():
            # numpy slices ride through: the local apply consumes them
            # directly and the client binary-encodes them (JSON fallback
            # listifies; "_width" lets it build roaring positions)
            sub: dict = {
                "columnIDs": cols[mask],
                "remote": True,
                "_width": width,
            }
            if values is not None:
                sub["values"] = values[mask]
            else:
                sub["rowIDs"] = rows[mask]
            if timestamps is not None:
                idxs = np.nonzero(mask)[0]
                sub["timestamps"] = [timestamps[i] for i in idxs]
            if req.get("clear"):
                sub["clear"] = True
            try:
                if node_id == self.cluster.node_id:
                    self.import_bits(index, f.name, sub)
                else:
                    self.client.import_bits(node_uri[node_id], index, f.name, sub)
            except Exception as e:
                errors.append(f"{node_id}: {e}")
        if errors:
            raise ApiError(
                "import partially failed on node(s): " + "; ".join(errors), 500
            )
        return True

    def import_roaring(self, index: str, field: str, shard: int, data: bytes, clear: bool = False, view: str = VIEW_STANDARD, remote: bool = False) -> dict:
        """Binary roaring import: the highest-throughput ingest path
        (reference api.go:367-427; call stack SURVEY §3.4). In cluster
        mode the batch is applied on every replica owning the shard
        (api.go:400-404)."""
        self._validate("ImportRoaring")
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError("field not found")
        if (
            not remote
            and self.cluster is not None
            and self.client is not None
            and len(self.cluster.nodes) > 1
        ):
            changed = 0
            errors: list[str] = []
            for node in self.cluster.shard_nodes(index, shard):
                try:
                    if node.id == self.cluster.node_id:
                        changed = self.import_roaring(
                            index, field, shard, data, clear=clear, view=view,
                            remote=True,
                        )["changed"]
                    else:
                        resp = self.client.import_roaring(
                            node.uri, index, field, shard, data, clear=clear,
                            view=view,
                        )
                        # All replicas apply the same batch; any replica's
                        # changed count is THE changed count.
                        if isinstance(resp, dict) and "changed" in resp:
                            changed = resp["changed"]
                except Exception as e:
                    errors.append(f"{node.id}: {e}")
            if errors:
                raise ApiError(
                    "import-roaring failed on replica(s): " + "; ".join(errors),
                    500,
                )
            return {"changed": changed}
        # Staged local apply: zero-copy decode into a staging buffer on
        # this handler thread, a coalesced merge on the import pool
        # (queued same-fragment batches group-commit into one apply; the
        # shared "changed" count is the group total), then a
        # double-buffered device upload overlapping the next batch's
        # merge.  One import-drain record spans the stages.
        with self.import_pool.drain_scope():
            try:
                buf = self.ingest.decode_roaring(data)
            except roaring.RoaringError as e:
                raise ApiError(f"bad roaring payload: {e}")

            def apply_group(payloads):
                # Per-payload merges under ONE pool job: the summed
                # "changed" equals the concat-then-merge count (a bit
                # two payloads both set counts once — the second merge
                # sees it already set), each merge sorts a modest batch
                # instead of one huge concatenation, and the group
                # still pays a single device sync.
                changed = 0
                frag = None
                for b in payloads:
                    result, frag = self._apply_roaring_positions(
                        index, f, shard, b.positions, clear, view
                    )
                    changed += result["changed"]
                return {"changed": changed}, frag

            handle = self.ingest.submit_segment(
                (index, f.name, view, int(shard), bool(clear)),
                buf,
                apply_group,
                release=lambda b: b.release(),
            )
            return handle.wait()

    def _apply_roaring(self, index: str, f, shard: int, data: bytes, clear: bool, view: str) -> dict:
        """Local roaring apply, state-gate-free (also the landing path for
        resize fragment transfers, which run while gated to RESIZING).
        Lock-step variant: decode + apply on the calling thread."""
        try:
            positions = roaring.deserialize(data)
        except roaring.RoaringError as e:
            raise ApiError(f"bad roaring payload: {e}")
        result, _frag = self._apply_roaring_positions(
            index, f, shard, positions, clear, view
        )
        return result

    def _apply_roaring_positions(
        self, index: str, f, shard: int, positions: np.ndarray, clear: bool,
        view: str,
    ) -> tuple[dict, object]:
        """Merge decoded roaring positions into the shard's fragment;
        returns (result, fragment) so the pipeline can hand the applied
        fragment to the device-upload stage."""
        width = f.n_words * 32
        rows = positions // np.uint64(width)
        cols_local = (positions % np.uint64(width)).astype(np.int64)
        v = f.create_view_if_not_exists(view)
        frag = v.create_fragment_if_not_exists(shard)
        changed = frag.import_bits(rows, cols_local, clear=clear)
        if view.startswith("bsig_") and f.is_bsi() and len(rows):
            # Restore bit depth from the transferred planes: schema carries
            # only FieldOptions, and depth auto-grows per node (reference
            # field.go:1050-1067) — without this a resize-transferred int
            # fragment would read as all-zero on the new owner.
            from pilosa_tpu.core.fragment import BSI_OFFSET_BIT

            f.grow_bit_depth(int(rows.max()) - BSI_OFFSET_BIT + 1)
        idx = self.holder.index(index)
        ef = idx.existence_field() if idx is not None else None
        if ef is not None and not clear and len(cols_local):
            ef.import_bits(
                np.zeros(len(cols_local), dtype=np.uint64),
                cols_local.astype(np.uint64) + np.uint64(shard) * np.uint64(width),
            )
        return {"changed": int(changed)}, frag

    # -- export (reference api.go:499-573 ExportCSV) ------------------------

    def export_csv(self, index: str, field: str, shard: int | None = None) -> str:
        self._validate("ExportCSV")
        f = self.holder.field(index, field)
        if f is None:
            raise NotFoundError("field not found")
        v = f.view(VIEW_STANDARD)
        out = io.StringIO()
        translator = self.executor.translator
        idx = self.holder.index(index)
        if v is not None:
            shards = sorted(v.fragments) if shard is None else [shard]
            for s in shards:
                frag = v.fragment(s)
                if frag is None:
                    continue
                width = frag.shard_width
                for row in frag.row_ids():
                    cols = frag.row_columns(row)
                    for c in cols:
                        col = int(c) + s * width
                        if f.keys:
                            rk = translator.translate_id(index, field, row)
                            row_out = rk
                        else:
                            row_out = row
                        if idx is not None and idx.keys:
                            col_out = translator.translate_id(index, "", col)
                        else:
                            col_out = col
                        out.write(f"{row_out},{col_out}\n")
        return out.getvalue()

    # -- cluster/info (reference api.go:1114-1342) --------------------------

    def _nodes_info(self) -> list[dict]:
        if self.cluster is not None:
            return self.cluster.nodes_info()
        return [{"id": self._node_id(), "uri": "", "isCoordinator": True, "state": "READY"}]

    def status(self) -> dict:
        self._validate("Status")
        nodes = self._nodes_info()
        # schema rides along for peer status exchange (the reference's
        # NodeStatus carries schema on gossip push/pull, gossip.go:321-357).
        out = {
            "state": self.state,
            "nodes": nodes,
            "localID": self._node_id(),
            "schema": self.holder.schema(),
            "availableShards": self.available_shards_map(),
        }
        if self.cluster is not None:
            # Resize visibility: followers' watchdogs poll this to tell a
            # coordinator still migrating from one that died mid-resize.
            out["coordinator"] = self.cluster.coordinator_id
            out["epoch"] = self.cluster.epoch
            out["resizePending"] = self.cluster.resize_pending
        return out

    def info(self) -> dict:
        self._validate("Info")
        from pilosa_tpu.shardwidth import SHARD_WIDTH_EXP

        return {"shardWidth": 1 << SHARD_WIDTH_EXP, "shardWidthExp": SHARD_WIDTH_EXP}

    def version(self) -> dict:
        return {"version": __version__}

    def hosts(self) -> list[dict]:
        self._validate("Hosts")
        # Membership only — skip status()'s full schema/shard-map build.
        return self._nodes_info()

    def shards_max(self) -> dict:
        """reference api.go MaxShards /internal/shards/max."""
        return {
            "standard": {
                name: max(idx.available_shards(), default=0)
                for name, idx in self.holder.indexes.items()
            }
        }

    # -- fragment internals (reference api.go:590-660 fragment block
    #    endpoints; used by anti-entropy sync and resize) -------------------

    def _fragment(self, index: str, field: str, view: str, shard: int):
        frag = self.holder.fragment(index, field, view, shard)
        if frag is None:
            raise NotFoundError(
                f"fragment not found: {index}/{field}/{view}/{shard}"
            )
        return frag

    def fragment_blocks(self, index: str, field: str, view: str, shard: int) -> dict:
        self._validate("FragmentBlocks")
        return {"blocks": self._fragment(index, field, view, shard).blocks()}

    def fragment_block_data(self, req: dict) -> dict:
        self._validate("FragmentBlockData")
        frag = self._fragment(
            req["index"], req["field"], req.get("view", VIEW_STANDARD),
            int(req["shard"]),
        )
        rows, cols = frag.block_data(int(req["block"]))
        return {"rows": rows, "cols": cols}

    def fragment_block_data_binary(self, req: dict) -> bytes | None:
        """Packed-binary block payload: the block's set bits as a roaring
        blob of row*width+col positions — a diverged 10M-bit block moves
        as compressed containers instead of JSON int lists (reference
        ships blocks via protobuf, encoding/proto/proto.go). None when a
        row id exceeds the position encoding (caller falls back to
        JSON)."""
        self._validate("FragmentBlockData")
        frag = self._fragment(
            req["index"], req["field"], req.get("view", VIEW_STANDARD),
            int(req["shard"]),
        )
        rows, cols = frag.block_data(int(req["block"]))
        width = frag.shard_width
        max_row = (2**64 - 1 - (width - 1)) // width
        if any(r > max_row for r in rows):
            return None
        positions = np.asarray(rows, dtype=np.uint64) * np.uint64(width) + np.asarray(
            cols, dtype=np.uint64
        )
        return roaring.serialize(np.sort(positions))

    def _attr_store(self, index: str, field: str | None):
        idx = self.holder.index(index)
        if idx is None:
            raise ApiError(f"index not found: {index}", 404)
        if not field:
            return idx.column_attrs
        f = idx.field(field)
        if f is None:
            raise ApiError(f"field not found: {field}", 404)
        return f.row_attrs

    def attr_blocks(self, index: str, field: str | None) -> dict:
        """Attr block checksums for anti-entropy diff (reference
        api.go:590-660 fragment/attr block endpoints; attr.go:81-120)."""
        self._validate("FragmentBlocks")
        store = self._attr_store(index, field)
        return {
            "blocks": [
                {"id": bid, "checksum": chk.hex()}
                for bid, chk in store.blocks()
            ]
        }

    def attr_block_data(self, req: dict) -> dict:
        self._validate("FragmentBlockData")
        store = self._attr_store(req["index"], req.get("field"))
        return {
            "attrs": {
                str(k): v
                for k, v in store.block_data(int(req["block"])).items()
            }
        }

    def fragment_data(self, index: str, field: str, view: str, shard: int) -> bytes:
        """Whole-fragment snapshot as a roaring blob (reference
        api.go FragmentData; fragment.go:2424-2594 tar WriteTo)."""
        self._validate("FragmentData")
        frag = self._fragment(index, field, view, shard)
        return roaring.serialize(frag.all_positions())

    def available_shards_map(self) -> dict:
        """{index: {field: [shards]}} of shards available cluster-wide as
        this node knows them (reference field.go AvailableShards union:
        local + remote)."""
        out: dict = {}
        for iname in self.holder.index_names():
            idx = self.holder.index(iname)
            if idx is None:
                continue
            fields = {}
            for fname in idx.field_names(include_internal=True):
                field = idx.field(fname)
                if field is not None:
                    fields[fname] = sorted(field.available_shards())
            out[iname] = fields
        return out

    def merge_available_shards(self, shard_map: dict) -> None:
        """Merge a peer's (or the resize coordinator's) shard-availability
        map (reference field.go:331-345 AddRemoteAvailableShards)."""
        for iname, fields in (shard_map or {}).items():
            idx = self.holder.index(iname)
            if idx is None:
                continue
            for fname, shards in fields.items():
                field = idx.field(fname)
                if field is not None:
                    field.add_remote_available_shards(shards)

    def fragment_inventory(self) -> list[dict]:
        """Every fragment this node holds, for resize planning (reference
        fragsByHost cluster.go:687)."""
        self._validate("FragmentData")
        out = []
        for iname in self.holder.index_names():
            idx = self.holder.index(iname)
            if idx is None:
                continue
            for fname in idx.field_names(include_internal=True):
                field = idx.field(fname)
                if field is None:
                    continue
                for vname in field.view_names():
                    for shard in sorted(field.view(vname).fragments):
                        out.append(
                            {
                                "index": iname,
                                "field": fname,
                                "view": vname,
                                "shard": shard,
                            }
                        )
        return out

    # -- control-plane observability (events / jobs / fragments) -----------

    def events_since(self, since: int = 0, limit: int | None = None) -> dict:
        """This node's local event journal past cursor ``since``."""
        return self.holder.events.since(since, limit)

    def cluster_events(self, since: int = 0) -> dict:
        """Cluster timeline: fan out to every peer's LOCAL journal and
        merge into one time-ordered view (coordinator view; any node can
        serve it).  Unreachable peers are reported, not fatal —
        a partitioned peer's missing events should read as "missing",
        the same contract as a truncated cursor."""
        local = self.holder.events.since(since)
        per_node = [local["events"]]
        unreachable = []
        if self.cluster is not None and self.client is not None:
            for node in self.cluster.nodes:
                if node.id == self.cluster.node_id or not node.uri:
                    continue
                try:
                    remote = self.client.debug_events(node.uri, since)
                except Exception as e:
                    unreachable.append({"node": node.id, "error": str(e)})
                    continue
                per_node.append(remote.get("events", []))
        merged = ev.merge_timelines(per_node)
        return {
            "events": merged,
            "nodes": len(per_node),
            "unreachable": unreachable,
        }

    def jobs_snapshot(self, kind: str | None = None) -> dict:
        """Background-job records (active + bounded history)."""
        return self.holder.jobs.snapshot(kind)

    def history_query(
        self,
        series=None,
        since: int | None = None,
        step: float | None = None,
        limit: int | None = None,
    ) -> dict | None:
        """This node's local metrics-history window (obs/history.py);
        None when the history plane is disabled."""
        if self.history is None:
            return None
        return self.history.query(
            series=series, since=since, step=step, limit=limit
        )

    def cluster_history(self, series=None, step: float | None = None) -> dict:
        """Cluster-merged metrics history: fan out to every peer's local
        rings and merge into ONE wall-clock-aligned timeline.  Alignment
        comes from downsampling every node onto the same absolute
        ``floor(t/step)*step`` grid (default: the local cadence), so
        sampler phase differences between nodes disappear; attribution
        is preserved by nesting points per node id under each series.
        Unreachable peers are reported, not fatal — same contract as
        cluster_events."""
        step = float(step) if step is not None else (
            self.history.cadence if self.history is not None else 1.0
        )
        local = self.history_query(series=series, step=step)
        merged: dict[str, dict[str, list]] = {}
        nodes: list[str] = []
        unreachable = []

        def fold(node_id: str, snap: dict | None) -> None:
            if not snap:
                return
            nodes.append(node_id)
            for name, pts in snap.get("series", {}).items():
                merged.setdefault(name, {})[node_id] = pts

        local_id = (
            self.cluster.node_id if self.cluster is not None
            else (local or {}).get("node", "")
        )
        fold(local_id, local)
        if self.cluster is not None and self.client is not None:
            for node in self.cluster.nodes:
                if node.id == self.cluster.node_id or not node.uri:
                    continue
                try:
                    remote = self.client.debug_history(
                        node.uri, series=series, step=step
                    )
                except Exception as e:
                    unreachable.append({"node": node.id, "error": str(e)})
                    continue
                fold(remote.get("node") or node.id, remote)
        return {
            "cluster": True,
            "step": step,
            "nodes": nodes,
            "series": merged,
            "unreachable": unreachable,
        }

    def slo_snapshot(self) -> dict:
        """Live per-op-class objective state (/debug/slo)."""
        return self.holder.slo.snapshot()

    def qos_snapshot(self) -> dict:
        """Cost-governed admission state (/debug/qos): per-tenant
        weighted-fair queue rows, ladder stages, shed/degraded counts
        and recent transitions (server/qos.py)."""
        if self.qos is None:
            return {"enabled": False, "tenants": {}, "transitions": []}
        return self.qos.snapshot()

    # -- trace plane (tail-sampled store, /debug/traces) --------------------

    def traces_snapshot(self, limit: int = 100) -> dict:
        """This node's kept-trace summaries + store counters."""
        store = self.holder.traces
        return {
            "traces": store.summaries(limit),
            "store": store.snapshot(),
        }

    def trace_detail(self, trace_id: str) -> dict | None:
        """One kept trace's spans (local view); None when not kept."""
        return self.holder.traces.detail(trace_id)

    def cluster_traces(self, limit: int = 100) -> dict:
        """Kept-trace summaries from every node, merged newest-first
        (same fan-out contract as :meth:`cluster_events`: unreachable
        peers are reported, not fatal)."""
        per_node = [self.holder.traces.summaries(limit)]
        unreachable = []
        if self.cluster is not None and self.client is not None:
            for node in self.cluster.nodes:
                if node.id == self.cluster.node_id or not node.uri:
                    continue
                try:
                    remote = self.client.debug_traces(node.uri, limit=limit)
                except Exception as e:
                    unreachable.append({"node": node.id, "error": str(e)})
                    continue
                per_node.append(remote.get("traces", []))
        merged = [t for traces in per_node for t in traces]
        merged.sort(key=lambda t: t.get("at", 0.0), reverse=True)
        return {
            "traces": merged[:limit],
            "nodes": len(per_node),
            "unreachable": unreachable,
        }

    def cluster_trace(self, trace_id: str) -> dict:
        """Assemble ONE trace cluster-wide: ask every node for the spans
        it holds under this trace id (kept or merely recent — a fast
        remote leg of a slow coordinator trace lives only in the peer's
        recent tier) and merge them into one span list."""
        spans = list(self.holder.traces.spans_for(trace_id))
        detail = self.holder.traces.detail(trace_id)
        nodes = 1
        unreachable = []
        if self.cluster is not None and self.client is not None:
            for node in self.cluster.nodes:
                if node.id == self.cluster.node_id or not node.uri:
                    continue
                try:
                    remote = self.client.debug_trace_spans(node.uri, trace_id)
                except Exception as e:
                    unreachable.append({"node": node.id, "error": str(e)})
                    continue
                spans.extend(remote.get("spans", []))
                nodes += 1
        spans.sort(key=lambda s: (s.get("startUnixMs", 0), s.get("node", "")))
        out = {
            "traceId": trace_id,
            "spans": spans,
            "nodes": nodes,
            "unreachable": unreachable,
        }
        if detail is not None:
            out["summary"] = {k: v for k, v in detail.items() if k != "spans"}
        return out

    def trace_spans(self, trace_id: str) -> dict:
        """Local spans for one trace id (the peer leg of
        :meth:`cluster_trace`)."""
        return {"spans": self.holder.traces.spans_for(trace_id)}

    # -- incident plane (flight recorder, /debug/incidents) -----------------

    def incidents_snapshot(self) -> dict:
        if self.flightrec is None:
            return {"enabled": False, "incidents": []}
        return self.flightrec.incidents_snapshot()

    def incident_detail(self, incident_id: str) -> dict | None:
        if self.flightrec is None:
            return None
        return self.flightrec.incident_detail(incident_id)

    # -- postmortem plane (black box, /debug/postmortem) --------------------

    def postmortem_snapshot(self, postmortem_id: str | None = None) -> dict | None:
        """Sealed crash bundles from this node's black box: the retained
        summaries + the newest bundle in full, or one bundle by id.
        None when the black box is disabled (no data dir) or the id is
        unknown."""
        if self.blackbox is None:
            return None
        if postmortem_id is not None:
            return self.blackbox.postmortem_detail(postmortem_id)
        return self.blackbox.postmortems()

    def cluster_postmortems(self) -> dict:
        """Every node's postmortem summaries, merged newest-first (same
        fan-out contract as :meth:`cluster_events`: unreachable peers
        are reported, not fatal).  Full bundles stay one ``?id=`` GET
        away on the owning node — a cluster merge of multi-MB bundles
        would be the wrong default."""
        local = self.postmortem_snapshot() or {"postmortems": []}
        merged = [
            dict(s, node=s.get("node") or (
                self.cluster.node_id if self.cluster is not None else ""
            ))
            for s in local.get("postmortems", [])
        ]
        nodes = 1
        unreachable = []
        if self.cluster is not None and self.client is not None:
            for node in self.cluster.nodes:
                if node.id == self.cluster.node_id or not node.uri:
                    continue
                try:
                    remote = self.client.debug_postmortem(node.uri)
                except Exception as e:
                    unreachable.append({"node": node.id, "error": str(e)})
                    continue
                nodes += 1
                for s in remote.get("postmortems", []):
                    merged.append(dict(s, node=s.get("node") or node.id))
        merged.sort(key=lambda s: s.get("assembledAt") or 0.0, reverse=True)
        return {
            "cluster": True,
            "postmortems": merged,
            "nodes": nodes,
            "unreachable": unreachable,
        }

    def fragment_details(
        self, index: str | None = None, field: str | None = None
    ) -> dict:
        """Per-fragment storage/residency introspection plus a
        holder-level aggregate and the device budget block
        (/debug/fragments)."""
        from pilosa_tpu.core import membudget, residency

        tracker = residency.default_tracker()
        fragments = []
        now = time.time()
        for iname in self.holder.index_names():
            if index is not None and iname != index:
                continue
            idx = self.holder.index(iname)
            if idx is None:
                continue
            for fname in idx.field_names(include_internal=True):
                if field is not None and fname != field:
                    continue
                fld = idx.field(fname)
                if fld is None:
                    continue
                for vname in fld.view_names():
                    view = fld.view(vname)
                    for shard in sorted(view.fragments):
                        frag = view.fragments[shard]
                        with frag._lock:
                            rows = len(frag._slot_of)
                            host_bytes = frag._host.nbytes
                            device_resident = frag._device is not None
                            device_bytes = (
                                frag._device_nbytes() if device_resident else 0
                            )
                            counts_cached = frag._counts is not None
                            op_n = frag.op_n
                            mut_version = frag.version
                            mut_epoch = frag.epoch
                            res_state = tracker.state_of(frag)
                            res_pinned = frag._res_pinned
                            res_heat = round(tracker.heat_of(frag), 3)
                        store = frag.store
                        last_snap = getattr(store, "last_snapshot_at", None)
                        # version-cached storage stats: repeat /debug/
                        # fragments polls (and the flight planner, which
                        # shares this cache) stop rescanning containers
                        # while the fragment is unchanged
                        prof = frag.container_profile()
                        d = {
                            "index": iname,
                            "field": fname,
                            "view": vname,
                            "shard": shard,
                            "rows": rows,
                            "bits": prof["bits"],
                            "containers": prof["containers"],
                            "hostBytes": host_bytes,
                            "deviceResident": device_resident,
                            "deviceBytes": device_bytes,
                            "countsCached": counts_cached,
                            "opLogLength": op_n,
                            # never resets (op_n rewinds on snapshot
                            # load; version is monotonic for the life of
                            # the fragment object, epoch fences rebuilt
                            # objects) — the cache-correctness pair
                            "version": mut_version,
                            "epoch": mut_epoch,
                            "residency": res_state,
                            "pinned": res_pinned,
                            "heat": res_heat,
                            "lastSnapshotAge": (
                                now - last_snap if last_snap else None
                            ),
                        }
                        fragments.append(d)
        totals = {
            "fragments": len(fragments),
            "bits": sum(f["bits"] for f in fragments),
            "hostBytes": sum(f["hostBytes"] for f in fragments),
            "deviceResident": sum(1 for f in fragments if f["deviceResident"]),
            "deviceBytes": sum(f["deviceBytes"] for f in fragments),
            "opLogLength": sum(f["opLogLength"] for f in fragments),
            "version": sum(f["version"] for f in fragments),
            "pinned": sum(1 for f in fragments if f["pinned"]),
            "staging": sum(
                1 for f in fragments if f["residency"] == residency.STATE_STAGING
            ),
        }
        return {
            "fragments": fragments,
            "totals": totals,
            "device": membudget.default_budget().snapshot(),
            "residency": tracker.snapshot(),
        }

    def resize_fetch(self, req: dict) -> dict:
        """Fetch and install the listed fragments from their source nodes
        (reference followResizeInstruction cluster.go:1272-1381). Runs
        while the cluster is gated to RESIZING."""
        self._validate("FragmentData")
        if self.client is None:
            raise ApiError("no internal client configured", 500)
        if req.get("schema"):
            # Joining node: install schema before fragment transfer
            # (reference cluster.go:1304-1323).
            self.holder.apply_schema(req["schema"])
            self._sync()
        instructions = req.get("instructions", [])
        job = self.holder.jobs.start("resize-fetch")
        job.set_phase("fetch")
        job.set_progress(fragments_total=len(instructions))
        fetched = 0
        try:
            for ins in instructions:
                index, fname = ins["index"], ins["field"]
                f = self.holder.field(index, fname)
                if f is None:
                    raise ApiError(
                        f"resize target missing schema for {index}/{fname}", 500
                    )
                data = self.client.retrieve_fragment(
                    ins["sourceURI"], index, fname, ins["view"], int(ins["shard"])
                )
                self._apply_roaring(
                    index, f, int(ins["shard"]), data, False, ins["view"]
                )
                fetched += 1
                job.advance(fragments_done=1, bytes_moved=len(data))
        except Exception as e:
            job.finish("aborted", error=f"{type(e).__name__}: {e}")
            raise
        job.finish("done")
        return {"fetched": fetched}

    # -- online migration (snapshot stream + op-log catch-up) ---------------
    #
    # Per-fragment migration for the online resize (cluster/resize.py):
    # the target pulls a pinned snapshot cut in resumable chunks
    # (ChunkPrefetcher overlaps fetch with apply, the PR-7 uploader
    # pattern pointed the other way), then replays op-log deltas in
    # bounded catch-up rounds while writes keep landing on the source.
    # Sessions stay open on the source until the post-flip finalize
    # drain.  ``faults.stage_fault`` hooks mark every phase boundary so
    # chaos tests can kill any participant at any point.

    _CATCHUP_ROUNDS = 5
    _SOURCE_ATTEMPTS = 3

    def _migration(self, token: str):
        try:
            return self.migrations.get(token)
        except KeyError as e:
            raise NotFoundError(str(e))

    def migrate_begin(self, req: dict) -> dict:
        """Source side: open a migration session — pin a snapshot cut
        and install the op-log delta tap (cluster/migration.py)."""
        self._validate("FragmentData")
        faults.stage_fault("source:begin")
        index, field = req["index"], req["field"]
        view = req.get("view", VIEW_STANDARD)
        shard = int(req["shard"])
        frag = self._fragment(index, field, view, shard)
        session = self.migrations.begin(frag, (index, field, view, shard))
        session.chunk_bytes = int(req.get("chunkBytes") or 0) or None
        return {
            "token": session.token,
            "size": session.size,
            "opN": int(getattr(frag, "op_n", 0)),
        }

    def migrate_chunk(self, token: str, offset: int) -> bytes:
        """Source side: one snapshot chunk.  Offset-addressed reads are
        idempotent, so a retried/restarted target resumes mid-stream."""
        self._validate("FragmentData")
        faults.stage_fault("source:chunk")
        session = self._migration(token)
        from pilosa_tpu.cluster import migration

        return session.chunk(
            int(offset), session.chunk_bytes or migration.CHUNK_BYTES
        )

    def migrate_delta(self, token: str) -> bytes:
        """Source side: drain one op-log catch-up round as a binary
        migrate frame (header carries ops-in-blob + ops still pending)."""
        self._validate("FragmentData")
        faults.stage_fault("source:delta")
        session = self._migration(token)
        blob, count, pending = session.delta()
        from pilosa_tpu.cluster import wire

        return wire.encode_migrate_frame(
            {"ops": count, "pending": pending}, blob
        )

    def migrate_end(self, token: str) -> dict:
        """Source side: close a session (uninstalls the delta tap)."""
        self._validate("FragmentData")
        self.migrations.end(token)
        return {}

    def migrate_fetch(self, req: dict) -> dict:
        """Target side: pull every listed fragment (snapshot stream +
        catch-up rounds) and HOLD the source sessions open; the
        coordinator flips ownership, then ``migrate_finalize`` drains
        the tail.  A crash here aborts only this target's instructions —
        its held source sessions expire via the registry TTL."""
        self._validate("FragmentData")
        if self.client is None:
            raise ApiError("no internal client configured", 500)
        if req.get("schema"):
            # Joining node: install schema before any fragment lands
            # (reference cluster.go:1304-1323).
            self.holder.apply_schema(req["schema"])
            self._sync()
        instructions = req.get("instructions", [])
        job = self.holder.jobs.start(
            "migrate-fetch", fragments=len(instructions)
        )
        job.set_phase("snapshot")
        job.set_progress(fragments_total=len(instructions))
        pulls = []
        try:
            for ins in instructions:
                pulls.append(self._migrate_pull(ins, job))
                job.advance(fragments_done=1)
        except Exception as e:
            for p in pulls:
                try:
                    self.client.migrate_end(p["uri"], p["token"])
                except Exception:  # graftlint: disable=exception-hygiene -- best-effort cleanup of held source sessions; the TTL sweep covers the rest
                    pass
            job.finish("aborted", error=f"{type(e).__name__}: {e}")
            raise
        with self._migrate_lock:
            for p in pulls:
                self._migrate_pulls[p["key"]] = p
        job.finish("done")
        return {"fetched": len(pulls)}

    def _migrate_pull(self, ins: dict, job) -> dict:
        """Pull one fragment, trying each listed source holder in turn
        (a dead source retries with seeded backoff, then the next
        replica takes over)."""
        import zlib as _zlib

        from pilosa_tpu.cluster.migration import CHUNK_BYTES

        index, fname = ins["index"], ins["field"]
        view = ins.get("view", VIEW_STANDARD)
        shard = int(ins["shard"])
        f = self.holder.field(index, fname)
        if f is None:
            raise ApiError(
                f"migrate target missing schema for {index}/{fname}", 500
            )
        sources = list(ins.get("sourceURIs") or [])
        if ins.get("sourceURI") and ins["sourceURI"] not in sources:
            sources.append(ins["sourceURI"])
        if not sources:
            raise ApiError(f"no source for {index}/{fname}/{shard}", 500)
        chunk_bytes = int(ins.get("chunkBytes") or CHUNK_BYTES)
        # Seeded by the fragment key: a chaos run's retry cadence
        # replays identically (testing/faults.py contract).
        rng = random.Random(
            _zlib.crc32(f"{index}/{fname}/{view}/{shard}".encode())
        )
        last_err: Exception | None = None
        for uri in sources:
            for attempt in range(self._SOURCE_ATTEMPTS):
                try:
                    return self._migrate_pull_from(
                        uri, index, f, view, shard, chunk_bytes, job
                    )
                except (ClientError, OSError) as e:
                    last_err = e
                    if attempt < self._SOURCE_ATTEMPTS - 1:
                        time.sleep(
                            0.05 * (2 ** attempt) * (0.5 + rng.random())
                        )
            logger.warning(
                "migrate pull of %s/%s/%s/%s from %s failed: %s",
                index, fname, view, shard, uri, last_err,
            )
        raise ApiError(
            f"migrate pull failed from every source for "
            f"{index}/{fname}/{view}/{shard}: {last_err}", 500
        )

    def _migrate_pull_from(
        self, uri: str, index: str, f, view: str, shard: int,
        chunk_bytes: int, job,
    ) -> dict:
        from pilosa_tpu.ingest.pipeline import ChunkPrefetcher

        begin = self.client.migrate_begin(
            uri, index, f.name, view, shard, chunk_bytes=chunk_bytes
        )
        token, size = begin["token"], int(begin["size"])
        try:
            buf = bytearray()
            pf = ChunkPrefetcher(
                lambda off: self.client.migrate_chunk(uri, token, off),
                size=size, chunk_bytes=chunk_bytes,
            )
            try:
                for _off, blob in pf:
                    buf += blob
                    job.advance(bytes_moved=len(blob))
            finally:
                pf.close()
            faults.stage_fault("target:apply")
            if buf:
                self._apply_roaring(index, f, shard, bytes(buf), False, view)
            # Bounded catch-up: writes kept landing on the source during
            # the snapshot stream; replay the accrued op-log delta until
            # lag reaches zero (or rounds exhaust — the post-flip
            # finalize drain is the backstop either way).
            job.set_phase("catch-up")
            lag = 0
            for _round in range(self._CATCHUP_ROUNDS):
                faults.stage_fault("target:catchup")
                header, blob = self.client.migrate_delta(uri, token)
                if blob:
                    self._apply_delta_ops(index, f, shard, view, blob)
                lag = int(header.get("pending", 0))
                job.annotate(
                    catchup_lag=lag, catchup_ops=int(header.get("ops", 0))
                )
                if lag == 0:
                    break
            return {
                "key": (index, f.name, view, shard),
                "uri": uri,
                "token": token,
                "lag": lag,
            }
        except Exception:
            try:
                self.client.migrate_end(uri, token)
            except Exception:  # graftlint: disable=exception-hygiene -- cleanup of a failed pull; the session TTL covers an unreachable source
                pass
            raise

    def _apply_delta_ops(
        self, index: str, f, shard: int, view: str, blob: bytes
    ) -> int:
        """Replay raw op-log records IN ORDER onto the local fragment —
        the catch-up half of migration.  In-order replay makes overlap
        with the snapshot cut harmless: the same ops apply in the same
        order the source applied them, and set/clear are idempotent."""
        applied = 0
        for op_type, payload, _opn in roaring.decode_ops(blob, 0):
            if op_type in (roaring.OP_ADD, roaring.OP_REMOVE):
                positions = np.array([payload], dtype=np.uint64)
            elif op_type in (roaring.OP_ADD_BATCH, roaring.OP_REMOVE_BATCH):
                positions = np.asarray(payload, dtype=np.uint64)
            else:
                positions = roaring.deserialize(payload)
            if not len(positions):
                continue
            clear = op_type in (
                roaring.OP_REMOVE, roaring.OP_REMOVE_BATCH,
                roaring.OP_REMOVE_ROARING,
            )
            self._apply_roaring_positions(
                index, f, shard, positions, clear, view
            )
            applied += 1
        return applied

    def migrate_finalize(self, req: dict) -> dict:
        """Target side, post-flip: drain the final op-log delta from
        each held source session and close it.  An unreachable source
        is non-fatal — anti-entropy heals whatever tail it buffered."""
        self._validate("FragmentData")
        instructions = req.get("instructions")
        with self._migrate_lock:
            if instructions is None:
                pulls = list(self._migrate_pulls.values())
                self._migrate_pulls.clear()
            else:
                pulls = []
                for ins in instructions:
                    key = (
                        ins["index"], ins["field"],
                        ins.get("view", VIEW_STANDARD), int(ins["shard"]),
                    )
                    p = self._migrate_pulls.pop(key, None)
                    if p is not None:
                        pulls.append(p)
        drained = 0
        for p in pulls:
            faults.stage_fault("target:finalize")
            index, fname, view, shard = p["key"]
            f = self.holder.field(index, fname)
            try:
                _header, blob = self.client.migrate_delta(
                    p["uri"], p["token"]
                )
                if blob and f is not None:
                    drained += self._apply_delta_ops(
                        index, f, int(shard), view, blob
                    )
                self.client.migrate_end(p["uri"], p["token"])
            except (ClientError, OSError) as e:
                logger.warning(
                    "finalize drain of %s from %s failed (anti-entropy"
                    " heals the tail): %s", p["key"], p["uri"], e,
                )
        return {"finalized": len(pulls), "ops": drained}

    def _clean_unowned_fragments(self) -> int:
        """Drop fragments this node no longer owns after a membership
        change (reference holderCleaner holder.go:898-926)."""
        if self.cluster is None or not hasattr(self.cluster, "owns_shard"):
            return 0
        dropped = 0
        for iname in self.holder.index_names():
            idx = self.holder.index(iname)
            if idx is None:
                continue
            for fname in idx.field_names(include_internal=True):
                field = idx.field(fname)
                if field is None:
                    continue
                for vname in field.view_names():
                    view = field.view(vname)
                    for shard in sorted(view.fragments):
                        if not self.cluster.owns_shard(
                            self.cluster.node_id, iname, shard
                        ):
                            view.drop_fragment(shard)
                            if self.store is not None:
                                self.store.delete_fragment(
                                    iname, fname, vname, shard
                                )
                            dropped += 1
        return dropped

    def receive_message(self, msg: dict) -> dict:
        """Handle a typed control-plane message from a peer (reference
        Server.receiveMessage switch, server.go:549-643)."""
        self._validate("ClusterMessage")
        from pilosa_tpu.cluster import broadcast as bc

        # Handlers call the _-prefixed internals: a cluster message must
        # apply even when this node's own state gates the public method
        # (e.g. a peer in STARTING receiving schema from the coordinator).
        t = msg.get("type")
        if t == bc.MSG_CREATE_INDEX:
            try:
                self._create_index(msg["index"], msg.get("options"), broadcast=False)
            except ConflictError:
                pass
        elif t == bc.MSG_DELETE_INDEX:
            try:
                self._delete_index(msg["index"], broadcast=False)
            except NotFoundError:
                pass
        elif t == bc.MSG_CREATE_FIELD:
            idx = self.holder.index(msg["index"])
            if idx is not None:
                try:
                    self._create_field(
                        msg["index"], msg["field"], msg.get("options"),
                        broadcast=False,
                    )
                except ConflictError:
                    pass
        elif t == bc.MSG_DELETE_FIELD:
            try:
                self._delete_field(msg["index"], msg["field"], broadcast=False)
            except NotFoundError:
                pass
        elif t == bc.MSG_CREATE_VIEW:
            f = self.holder.field(msg["index"], msg["field"])
            if f is not None:
                f.create_view_if_not_exists(msg["view"])
        elif t == bc.MSG_CREATE_SHARD:
            f = self.holder.field(msg["index"], msg["field"])
            if f is not None:
                f.add_remote_available_shards([int(msg["shard"])])
        elif t == bc.MSG_CLUSTER_STATUS:
            if self.cluster is not None and hasattr(self.cluster, "set_state"):
                nodes = msg.get("nodes")
                if nodes:
                    # Membership commit from the resize coordinator
                    # (reference mergeClusterStatus cluster.go:1918-1978).
                    from pilosa_tpu.cluster.topology import Node as CNode

                    if msg.get("coordinator"):
                        self.cluster.coordinator_id = msg["coordinator"]
                    self.cluster.disabled = False
                    old_ids = {n.id for n in self.cluster.nodes}
                    new_ids = {n["id"] for n in nodes}
                    for nid in sorted(new_ids - old_ids):
                        self.holder.events.record(ev.EVENT_NODE_JOIN, peer=nid)
                    for nid in sorted(old_ids - new_ids):
                        self.holder.events.record(ev.EVENT_NODE_LEAVE, peer=nid)
                    self.cluster.set_static(
                        [CNode(id=n["id"], uri=n.get("uri", "")) for n in nodes]
                    )
                self.cluster.set_state(msg["state"])
                if msg.get("availableShards"):
                    self.merge_available_shards(msg["availableShards"])
                still_member = not nodes or any(
                    n["id"] == self.cluster.node_id for n in nodes
                )
                if nodes and msg["state"] == STATE_NORMAL and still_member:
                    # A removed node keeps its data (the reference expects
                    # it to shut down; its fragments were re-sourced).
                    self._clean_unowned_fragments()
        elif t == bc.MSG_RESIZE_PREPARE:
            # Per-fragment migration begins: remember the PENDING
            # membership + epoch so flips can route flipped shards onto
            # the new ring while everything else stays put.  The cluster
            # state stays NORMAL — reads and writes keep flowing.
            if self.cluster is not None and hasattr(self.cluster, "begin_resize"):
                from pilosa_tpu.cluster.topology import Node as CNode

                pending = [
                    CNode(id=n["id"], uri=n.get("uri", ""))
                    for n in msg.get("nodes", [])
                ]
                epoch = self.cluster.begin_resize(pending, msg.get("epoch"))
                self.holder.events.record(
                    ev.EVENT_RESIZE_PHASE, phase="prepare", epoch=epoch,
                )
        elif t == bc.MSG_EPOCH_FLIP:
            # One shard's ownership flips to the pending ring.
            if self.cluster is not None and hasattr(self.cluster, "flip_shard"):
                if self.cluster.flip_shard(
                    msg["index"], int(msg["shard"]), msg.get("epoch")
                ):
                    self.holder.events.record(
                        ev.EVENT_EPOCH_FLIP,
                        index=msg["index"], shard=int(msg["shard"]),
                        epoch=msg.get("epoch"),
                    )
        elif t == bc.MSG_RESIZE_CANCEL:
            if self.cluster is not None and hasattr(self.cluster, "abort_resize"):
                self.cluster.abort_resize()
                self.holder.events.record(
                    ev.EVENT_RESIZE_ABORT, reason=msg.get("reason", ""),
                )
        elif t == bc.MSG_NODE_STATE:
            if self.cluster is not None and hasattr(self.cluster, "mark_node_state"):
                self.cluster.mark_node_state(msg["node"], msg["state"])
        elif t == bc.MSG_SET_COORDINATOR:
            # coordinator (= translation primary) moved (reference
            # SetCoordinatorMessage handling, server.go:549-643)
            if self.cluster is not None and msg.get("coordinator"):
                self.cluster.coordinator_id = msg["coordinator"]
                for n in self.cluster.nodes:
                    n.is_coordinator = n.id == msg["coordinator"]
        elif t == bc.MSG_RECALCULATE_CACHES:
            pass  # device row counts are exact; no cache to rebuild
        return {}

    def translate_keys(self, index: str, field: str | None, keys: list[str]) -> list[int]:
        self._validate("TranslateKeys")
        return self.executor.translator.translate_keys(index, field or "", keys)

    def translate_ids(self, index: str, field: str | None, ids: list[int]) -> list[str]:
        self._validate("TranslateKeys")
        return self.executor.translator.translate_ids(index, field or "", ids)

    def translate_log(self, offset: int) -> dict:
        """Entry-log feed for replica streaming (reference
        translate.go:91-97): entries since ``offset`` from the LOCAL
        store plus its total length (replicas detect a restarted/
        shorter primary log by the length)."""
        self._validate("TranslateKeys")
        translator = self.executor.translator
        local = getattr(translator, "local", translator)
        entries, new_offset = local.log_entries(int(offset))
        return {
            "entries": [list(e) for e in entries],
            "offset": new_offset,
            "len": local.log_len(),
        }

    def translate_restore(self, entries: list) -> dict:
        """Install exact (index, field, key, id) mappings — the restore
        half of backup's translation dump (set_mapping bypasses
        read-only, the same path replica streaming uses).  In cluster
        mode the restore is FORWARDED to the translation primary: only
        its store allocates future ids, so installing on a replica
        alone would let the primary re-allocate colliding ids; replicas
        then converge via log streaming."""
        self._validate("TranslateKeys")
        translator = self.executor.translator
        if (
            self.cluster is not None
            and self.client is not None
            and hasattr(translator, "_is_primary")
            and not translator._is_primary()
        ):
            primary = self.cluster.translate_primary()
            return self.client.translate_restore(primary.uri, entries)
        local = getattr(translator, "local", translator)
        for index, field, key, id_ in entries:
            local.set_mapping(index, field, [key], [int(id_)])
        return {"restored": len(entries)}

    def resize_abort(self) -> dict:
        """Abort/clear a resize: re-commit the CURRENT membership with
        state NORMAL on every reachable node (reference api.go:1249
        ResizeAbort).  Our resize runs synchronously and self-aborts on
        failure, so this is the operator's recovery hammer for a
        cluster left in RESIZING by a mid-resize coordinator crash.
        Valid only on the coordinator."""
        self._validate("ResizeAbort")
        if self.cluster is None:
            raise ApiError("cluster not configured", 400)
        if not self.cluster.is_coordinator:
            raise ApiError("resize-abort must run on the coordinator", 400)
        from pilosa_tpu.cluster.resize import ResizeCoordinator

        rc = ResizeCoordinator(self.cluster, self.client, self)
        nodes = list(self.cluster.nodes)
        rc._commit_membership(nodes, nodes)
        # The operator chose to abandon the interrupted plan: drop the
        # journal so a later resume() can't replay a dead resize.
        rc._delete_journal()
        return {"aborted": True}

    def resize_remove_node(self, node_id: str) -> dict:
        """Remove a node through the resize protocol (reference
        api.go:1214 RemoveNode + POST /cluster/resize/remove-node).
        Valid only on the coordinator."""
        self._validate("RemoveNode")
        if self.cluster is None:
            raise ApiError("cluster not configured", 400)
        if not self.cluster.is_coordinator:
            raise ApiError("remove-node must run on the coordinator", 400)
        if self.cluster.node(node_id) is None:
            raise ApiError(f"unknown node: {node_id}", 400)
        from pilosa_tpu.cluster.resize import ResizeCoordinator, ResizeError

        try:
            ResizeCoordinator(self.cluster, self.client, self).remove_node(
                node_id
            )
        except ResizeError as e:
            raise ApiError(str(e), 400)
        return {"removed": node_id}

    def resize_resume(self) -> dict:
        """Resume an interrupted resize from the persisted journal (a
        coordinator crash mid-migration leaves a resumable plan behind;
        re-dispatch is idempotent).  Valid only on the coordinator."""
        if self.cluster is None:
            raise ApiError("cluster not configured", 400)
        if not self.cluster.is_coordinator:
            raise ApiError("resize-resume must run on the coordinator", 400)
        from pilosa_tpu.cluster.resize import ResizeCoordinator, ResizeError

        try:
            return ResizeCoordinator(self.cluster, self.client, self).resume()
        except ResizeError as e:
            raise ApiError(str(e), 400)

    def set_coordinator(self, node_id: str) -> dict:
        """Move the coordinator (and with it the translation-primary
        role) to ``node_id``, broadcasting so every live node converges
        (reference api.go:1192-1256 SetCoordinator + the
        SetCoordinatorMessage broadcast).  Used for takeover after a
        dead coordinator: any surviving node accepts this call."""
        if self.cluster is None:
            raise ApiError("cluster not configured", 400)
        if self.cluster.node(node_id) is None:
            raise ApiError(f"unknown node: {node_id}", 400)
        import pilosa_tpu.cluster.broadcast as bc

        self.cluster.coordinator_id = node_id
        for n in self.cluster.nodes:
            n.is_coordinator = n.id == node_id
        if self.broadcaster is not None:
            try:
                self.broadcaster.send_sync(
                    {"type": bc.MSG_SET_COORDINATOR, "coordinator": node_id}
                )
            except Exception:
                # best-effort: takeover typically runs BECAUSE a node is
                # dead; survivors converged, the dead node re-learns the
                # coordinator from ClusterStatus on rejoin
                logger.warning(
                    "set-coordinator broadcast incomplete", exc_info=True
                )
        return {"coordinator": node_id}

    def _node_id(self) -> str:
        if self.store is not None:
            return self.store.node_id()
        return "local"

    def _sync(self) -> None:
        if self.store is not None:
            self.store.sync()

    def close(self) -> None:
        self.migrations.close()  # detach any live delta taps
        if self.flightrec is not None:
            self.flightrec.stop()
        if self.batcher is not None:
            self.batcher.close()  # drains the admission queue first
        self.ingest.close()  # flush pending device uploads
        self.import_pool.close()
        if self.store is not None:
            self.store.close()
