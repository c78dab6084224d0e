"""Device cost ledger (obs/devledger.py): site registration, compile vs
cache-hit detection, tenant/principal attribution through the serving
stack, the recompile-storm detector, and the HTTP surfaces."""

import http.client
import json
import urllib.parse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.obs import devledger, tracing


@pytest.fixture(autouse=True)
def _clean_ledger():
    """The ledger is process-global by design; every test starts zeroed
    (sites and the monitoring listener survive reset)."""
    devledger.reset()
    yield
    devledger.reset()
    devledger.configure_storm(threshold=8, window_s=60.0, warmup_s=0.0)


def _drain_stash():
    """Adopt any compile events stashed on this thread by input setup
    (jnp.asarray & co. compile tiny programs too) so they cannot leak
    into the assertions that follow."""
    devledger.site("test.drain").claim()


class TestSitesAndCounters:
    def test_site_registration_is_idempotent(self):
        a = devledger.site("test.reg")
        b = devledger.site("test.reg")
        assert a is b

    def test_recording_flows_to_counters_and_snapshot(self):
        s = devledger.site("test.rec")
        s.record_launch(0.002, n=3)
        s.record_transfer(1024, "h2d")
        s.record_transfer(256, "d2h")
        s.record_compile(0.01, sig="shape[8]")
        c = devledger.counters()
        assert c["site.test.rec.launches"] == 3
        assert c["site.test.rec.transferBytes"] == 1280
        assert c["site.test.rec.compiles"] == 1
        assert c["launches"] >= 3 and c["compiles"] >= 1
        snap = devledger.snapshot()
        row = snap["sites"]["test.rec"]
        assert row["h2dBytes"] == 1024 and row["d2hBytes"] == 256
        assert row["recentCompileSigs"] == ["shape[8]"]
        assert snap["totals"]["compiles"] >= 1

    def test_prometheus_text_has_all_families(self):
        s = devledger.site("test.prom")
        s.record_launch(0.001)
        s.record_transfer(64, "h2d")
        text = devledger.prometheus_text()
        for fam in (
            "pilosa_dev_compiles",
            "pilosa_dev_launches",
            "pilosa_dev_device_ms",
            "pilosa_dev_transfer_bytes",
            "pilosa_dev_tenant_launches",
        ):
            assert fam in text
        assert 'site="test.prom"' in text

    def test_clean_tenant_bounds_and_sanitizes(self):
        assert devledger.clean_tenant(None) == devledger.DEFAULT_TENANT
        assert devledger.clean_tenant("  acme  ") == "acme"
        assert devledger.clean_tenant('ev"il{x}\\') == "evilx"
        assert len(devledger.clean_tenant("x" * 500)) == 64


class TestCompileVsCacheHit:
    def test_window_adopts_real_compile_then_cache_hit(self):
        s = devledger.site("test.jit")
        fn = jax.jit(lambda x: x * 2 + 1)
        x = jnp.arange(7, dtype=jnp.int32)
        _drain_stash()
        with s.launch(sig="warm i32[7]"):
            fn(x).block_until_ready()
        after_first = s.snapshot()
        assert after_first["compiles"] >= 1, "first call must XLA-compile"
        assert after_first["launches"] == 1
        with s.launch(sig="hit i32[7]"):
            fn(x).block_until_ready()
        after_second = s.snapshot()
        assert after_second["compiles"] == after_first["compiles"], (
            "jit cache hit must not count as a compile"
        )
        assert after_second["launches"] == 2

    def test_track_identity_signals_first_sight(self):
        s = devledger.site("test.track")
        fn = lambda x: x  # noqa: E731 - identity is what's tracked
        assert s.track(fn, ((4, 4), "f32")) is True
        assert s.track(fn, ((4, 4), "f32")) is False
        assert s.track(fn, ((8, 4), "f32")) is True
        assert s.snapshot()["cacheHits"] == 1
        assert s.snapshot()["trackedIdentities"] == 2

    def test_claim_prefers_innermost_window(self):
        outer = devledger.site("test.outer")
        inner = devledger.site("test.inner")
        fn = jax.jit(lambda x: x - 3)
        x = jnp.arange(11, dtype=jnp.int32)
        _drain_stash()
        with outer.launch(sig="mesh-ish"):
            fn(x).block_until_ready()
            # the post-hoc funnel inside the window claims the compile
            # for the more specific site
            inner.claim(sig="kernel i32[11]")
        assert inner.snapshot()["compiles"] >= 1
        assert outer.snapshot()["compiles"] == 0

    def test_stashed_compile_claimed_without_window(self):
        s = devledger.site("test.stash")
        fn = jax.jit(lambda x: x + 100)
        x = jnp.arange(13, dtype=jnp.int32)
        _drain_stash()
        fn(x).block_until_ready()  # no window: events land in the stash
        assert s.claim(sig="post-hoc") >= 1
        assert s.snapshot()["compiles"] >= 1

    def test_muted_window_books_nothing(self):
        s = devledger.site("test.muted")
        fn = jax.jit(lambda x: x ^ 5)
        x = jnp.arange(17, dtype=jnp.int32)
        _drain_stash()
        with s.launch(sig="aot", muted=True):
            fn(x).block_until_ready()
        snap = s.snapshot()
        assert snap["compiles"] == 0 and snap["launches"] == 0

    def test_compile_annotates_active_trace_span(self):
        tracer = tracing.RecordingTracer()
        old = tracing.get_tracer()
        tracing.set_tracer(tracer)
        try:
            s = devledger.site("test.span")
            fn = jax.jit(lambda x: x * 31)
            x = jnp.arange(19, dtype=jnp.int32)
            _drain_stash()
            with tracing.start_span("test.query") as sp:
                with s.launch(sig="i32[19]"):
                    fn(x).block_until_ready()
            assert int(sp.tags.get("xlaCompiles", 0)) >= 1
            assert sp.tags.get("xlaCompileMs", 0) > 0
            assert any(
                site.startswith("test.span ")
                for site in sp.tags.get("xlaCompileSites", [])
            )
        finally:
            tracing.set_tracer(old)


class TestPrincipals:
    def test_tenant_scope_threads_to_bookings(self):
        s = devledger.site("test.tenant")
        with devledger.tenant_scope("acme"):
            with devledger.principal_scope("idx", "read.count"):
                assert devledger.current_principal() == (
                    "acme", "idx", "read.count",
                )
                s.record_launch(0.001)
                s.record_transfer(512, "h2d")
        assert devledger.current_tenant() == devledger.DEFAULT_TENANT
        rows = {
            (p["tenant"], p["index"], p["opClass"]): p
            for p in devledger.snapshot()["principals"]
        }
        row = rows[("acme", "idx", "read.count")]
        assert row["launches"] == 1 and row["h2dBytes"] == 512

    def test_weighted_scope_splits_flight_across_tenants(self):
        s = devledger.site("test.flight")
        weights = (
            (("alpha", "i", "read.count"), 0.75),
            (("beta", "i", "read.count"), 0.25),
        )
        with devledger.weighted_scope(weights):
            s.record_launch(0.004)
            s.record_transfer(1000, "h2d")
        rows = {
            p["tenant"]: p for p in devledger.snapshot()["principals"]
        }
        # every rider books at least one launch; bytes split by weight
        assert rows["alpha"]["launches"] == 1
        assert rows["beta"]["launches"] == 1
        assert rows["alpha"]["h2dBytes"] == 750
        assert rows["beta"]["h2dBytes"] == 250

    def test_batcher_flight_carries_submitters_principal(self):
        from pilosa_tpu.server.api import API

        api = API(batch_window=0.001, batch_max_size=16, rescache_entries=0)
        try:
            api.create_index("dl")
            api.create_field("dl", "f")
            rng = np.random.default_rng(5)
            width = api.holder.n_words * 32
            writes = " ".join(
                f"Set({int(c)}, f={row})"
                for row in range(4)
                for c in rng.integers(0, width, size=64)
            )
            api.query("dl", writes)
            q = "Count(Intersect(Row(f=0), Row(f=1)))"
            with devledger.tenant_scope("acme"):
                # repeats push the pair path past its single-query warm
                # gate (cold queries ride the unledgered host tier)
                for _ in range(8):
                    api.query("dl", q)
            acme = [
                p
                for p in devledger.snapshot()["principals"]
                if p["tenant"] == "acme"
            ]
            assert acme, "tenant principal must survive the batcher demux"
            assert any(
                p["opClass"] == "read.count" and p["launches"] > 0
                for p in acme
            )
            assert devledger.counters()["site.ops.kernels.launches"] > 0
        finally:
            api.close()


class TestStormDetector:
    def test_storm_fires_once_at_threshold_and_cools_down(self):
        events = []
        devledger.on_storm(events.append)
        devledger.configure_storm(threshold=3, window_s=60.0, warmup_s=0.0)
        devledger.mark_warm()
        s = devledger.site("test.storm")
        for i in range(3):
            s.record_compile(0.001, sig=f"shape[{i}]")
        assert len(events) == 1, "storm must fire exactly at the threshold"
        bundle = events[0]
        assert bundle["type"] == "recompile-storm"
        assert bundle["count"] == 3 and bundle["threshold"] == 3
        assert bundle["sites"] == {"test.storm": 3}
        assert bundle["shapes"][-1] == "shape[2]"
        # inside the cooldown window further compiles extend no new storm
        s.record_compile(0.001, sig="shape[3]")
        assert len(events) == 1
        assert devledger.snapshot()["storm"]["recent"][0]["count"] == 3

    def test_cold_ledger_never_storms(self):
        events = []
        devledger.on_storm(events.append)
        devledger.configure_storm(threshold=2, window_s=60.0, warmup_s=3600.0)
        s = devledger.site("test.coldstorm")
        for i in range(5):
            s.record_compile(0.001, sig=f"s{i}")
        assert events == [], "pre-warmup compiles are expected, not a storm"


def _http_get(uri, path, headers=None):
    netloc = urllib.parse.urlsplit(uri).netloc
    conn = http.client.HTTPConnection(netloc, timeout=30)
    try:
        conn.request("GET", path, headers=headers or {})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _http_post(uri, path, body, headers=None):
    netloc = urllib.parse.urlsplit(uri).netloc
    conn = http.client.HTTPConnection(netloc, timeout=30)
    try:
        h = {"Content-Type": "application/json"}
        h.update(headers or {})
        conn.request("POST", path, body=body, headers=h)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


class TestHTTPEndToEnd:
    def test_two_tenants_attributed_through_the_wire(self):
        from pilosa_tpu.server.node import NodeServer

        srv = NodeServer(port=0, batch_window=0.001, rescache_entries=0)
        srv.start()
        try:
            uri = srv.uri
            st, _ = _http_post(uri, "/index/t2", b"{}")
            assert st in (200, 201)
            st, _ = _http_post(uri, "/index/t2/field/f", b"{}")
            assert st in (200, 201)
            rng = np.random.default_rng(11)
            width = srv.api.holder.n_words * 32
            writes = " ".join(
                f"Set({int(c)}, f={row})"
                for row in range(12)
                for c in rng.integers(0, width, size=48)
            )
            st, _ = _http_post(
                uri, "/index/t2/query", json.dumps({"query": writes}).encode()
            )
            assert st == 200
            # distinct pair queries with repeated field demand: identical
            # repeats would be absorbed before the device, and cold
            # singles ride the unledgered host tier
            pairs = [(a, b) for a in range(5) for b in range(a + 1, 5)]
            for i, (a, b) in enumerate(pairs * 2):
                tenant = "alpha" if i % 2 == 0 else "beta"
                q = f"Count(Intersect(Row(f={a}), Row(f={b})))"
                st, _ = _http_post(
                    uri,
                    "/index/t2/query",
                    json.dumps({"query": q}).encode(),
                    headers={devledger.TENANT_HEADER: tenant},
                )
                assert st == 200
            st, body = _http_get(uri, "/debug/devcosts")
            assert st == 200
            snap = json.loads(body)
            assert snap["totals"]["launches"] > 0
            site_launches = {
                name: row["launches"] for name, row in snap["sites"].items()
            }
            assert sum(site_launches.values()) > 0
            tenants = {
                p["tenant"]: p
                for p in snap["principals"]
                if p["tenant"] in ("alpha", "beta")
            }
            assert set(tenants) == {"alpha", "beta"}, (
                f"both tenants must have principal rows: {snap['principals']}"
            )
            for p in tenants.values():
                assert p["index"] == "t2"
                assert p["opClass"] == "read.count"
            # the same accounting must surface on /metrics and /debug/vars
            st, body = _http_get(uri, "/metrics")
            assert st == 200
            text = body.decode()
            assert "pilosa_dev_launches" in text
            assert 'tenant="alpha"' in text
            st, body = _http_get(uri, "/debug/vars")
            assert st == 200
            assert "devledger" in json.loads(body)
        finally:
            srv.stop()
