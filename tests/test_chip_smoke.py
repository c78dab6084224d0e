"""chip_smoke.py's stages under JAX_PLATFORMS=cpu at a tiny shape, its
verdict logic on canned server payloads, and the start-up contracts the
smoke relies on: no CPU stand-in in ``cli server``, a compile cache with
one fixed place.  None of this is a chip run; ``python chip_smoke.py`` on
a TPU is."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _child_env(**extra) -> dict:
    env = dict(os.environ, PYTHONPATH=REPO, **extra)
    return env


# -- the stages, against a server started through the CLI --------------------


def test_stages_agree_with_reference_and_survive_restart(tmp_path):
    """Reference and server agree on every class of request; SIGTERM
    exits 0; the restarted server reads the acknowledged writes back and
    is served by the persistent compile cache."""
    shards = 4
    env = _child_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    port = chip_smoke.free_port()
    srv = chip_smoke.Server(
        str(tmp_path / "data"), port, str(tmp_path / "server.log"), env=env
    )
    try:
        srv.start()
        c = chip_smoke.Client(port)
        width = int(c.get("/info")["shardWidth"])
        assert width == 1 << int(os.environ["PILOSA_TPU_SHARD_WIDTH"])
        ref = chip_smoke.Reference(shards, width)
        chip_smoke.stage_schema(c)
        load = chip_smoke.stage_load(port, ref, seed=5, loaders=2)
        assert load["bits"] > 0 and load["values"] > 0
        chk = chip_smoke.Checker()
        reads = chip_smoke.stage_reads(port, ref, chk, seed=5)
        writes = chip_smoke.stage_writes(
            port, ref, chk, 5, reads["wide_flight"]
        )
        assert chk.mismatches == []
        for cls in ("pair_count", "topn", "groupby", "bsi_aggregate",
                    "bsi_range", "ast_count", "write", "readback",
                    "pair_count_after_write"):
            assert chk.requests.get(cls), cls
        dbg = c.get("/debug/vars")
        diag = c.get("/internal/diagnostics")
        c.close()
        # on the CPU the verdict must fail, and only for being on the CPU
        keys = {k for k, _ in chip_smoke.verdict(dbg, diag, {
            "stack_bytes": 1, "loaded_bytes": 1,
        })}
        assert "platform" in keys
        assert keys <= chip_smoke.CPU_EXPECTED, keys

        assert srv.terminate() == 0
        srv.start()
        chk2 = chip_smoke.Checker()
        second = chip_smoke.stage_restarted(port, ref, chk2, 5, writes)
        assert chk2.mismatches == []
        assert second["persistent_cache_hits"] > 0
        assert srv.terminate() == 0
    finally:
        srv.kill()


def test_reference_semantics_on_a_hand_made_index():
    ref = chip_smoke.Reference(shards=2, width=64)
    ref.load_shard(0, "f", np.array([0, 0, 1]), np.array([1, 9, 9]))
    ref.load_shard(1, "f", np.array([0, 1]), np.array([5, 5]))
    a, b = ref.row("f", 0), ref.row("f", 1)
    assert ref.count(a) == 3 and ref.count(b) == 2
    assert ref.count(ref.combine("Intersect", [a, b])) == 2
    assert ref.count(ref.combine("Union", [a, b])) == 3
    assert ref.count(ref.combine("Difference", [a, b])) == 1
    assert ref.count(ref.combine("Xor", [a, b])) == 1
    assert ref.columns("f", 0) == [1, 9, 64 + 5]
    ref.set_bit("f", 1, 70, True)
    ref.set_bit("f", 0, 9, False)
    assert ref.get_bit("f", 1, 70) and not ref.get_bit("f", 0, 9)
    assert ref.row_counts("f", ref.row("f", 1)).tolist()[:2] == [1, 3]


# -- the verdict, on canned /debug/vars + /internal/diagnostics payloads ------

_EXPECT = {"stack_bytes": 1000, "loaded_bytes": 800}


def _good():
    dbg = {
        "kernels": {
            "dispatch_lanes": {"pallas": 5, "xla": 9, "host": 3},
            "gram_gates": {
                "self": {"ok": True, "fails": 0},
                "cross": {"ok": True, "fails": 0},
            },
            "pallas_fallbacks": 0,
            "transfer_bytes": {"h2d": 5000, "d2h": 10},
            "counters": {"kernel_dispatch{kernel:gram_gather,lane:pallas}": 5},
        },
        "counters": {"http_requests{route:query}": 40},
        "dist": {"meshFallbacks": 0},
        "device": {"capBytes": 12_000_000_000, "usedBytes": 4000},
        "ingest": {"uploader": {"uploads": 12}},
        "batcher": {"coalesced": 30},
        "devledger": {"sites": {
            s: {"launches": 3}
            for s in ("ops.kernels", "ops.bsi", "exec.astbatch", "ingest.upload")
        }},
        "native": {
            "libpilosa_hostops": {"path": "/r/native/a.so", "built": True},
            "libpilosa_native": {"path": "/r/native/b.so", "built": True},
        },
    }
    diag = {"system": {"devices": [
        {"id": 0, "platform": "tpu", "kind": "TPU v5 lite", "bytesInUse": 4000},
    ]}}
    return dbg, diag


def test_result_line_is_exactly_ok_and_device():
    """The driver reads the last line of stdout and takes nothing but
    ``ok`` and ``device{platform, kind, count}``; the report is elsewhere."""
    _, diag = _good()
    d = diag["system"]["devices"]
    line = chip_smoke.result_line(
        True, {"platform": d[0]["platform"], "kind": d[0]["kind"], "count": len(d)})
    assert "\n" not in line
    got = json.loads(line)
    assert got == {"ok": True,
                   "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(got) == ["ok", "device"]
    assert list(got["device"]) == ["platform", "kind", "count"]
    assert type(got["device"]["count"]) is int
    assert json.loads(chip_smoke.result_line(0, got["device"]))["ok"] is False


def test_verdict_passes_a_clean_chip_payload():
    dbg, diag = _good()
    assert chip_smoke.verdict(dbg, diag, _EXPECT) == []


def _platform_cpu(dbg, diag):
    diag["system"]["devices"][0]["platform"] = "cpu"


def _gate_false(dbg, diag):
    dbg["kernels"]["gram_gates"]["self"] = {"ok": False, "fails": 3}


def _demoted(dbg, diag):
    dbg["kernels"]["counters"]["kernel_demotions{kernel:gram_gather,lane:xla}"] = 1


def _mesh_fallback(dbg, diag):
    dbg["counters"]["dist_mesh_fallback_total"] = 1


def _no_pallas(dbg, diag):
    del dbg["kernels"]["dispatch_lanes"]["pallas"]


def _fallbacks(dbg, diag):
    dbg["kernels"]["pallas_fallbacks"] = 2


def _cap_unlimited(dbg, diag):
    dbg["device"]["capBytes"] = None


def _stacks_missing(dbg, diag):
    dbg["device"]["usedBytes"] = 10


def _native_missing(dbg, diag):
    dbg["native"]["libpilosa_hostops"] = {"path": None, "error": "g++: not found"}


def _cross_unprobed(dbg, diag):
    dbg["kernels"]["gram_gates"]["cross"] = {"ok": None, "fails": 0}


@pytest.mark.parametrize("mutate,key", [
    (_platform_cpu, "platform"),
    (_gate_false, "gate.self"),
    (_demoted, "demoted"),
    (_mesh_fallback, "mesh_fallback"),
    (_no_pallas, "lanes.pallas"),
    (_fallbacks, "fallbacks"),
    (_cap_unlimited, "device.cap"),
    (_stacks_missing, "device.used"),
    (_native_missing, "native"),
    (_cross_unprobed, "gate.cross"),
])
def test_verdict_fails_each_way_the_chip_can_be_bypassed(mutate, key):
    dbg, diag = copy.deepcopy(_good())
    mutate(dbg, diag)
    keys = [k for k, _ in chip_smoke.verdict(dbg, diag, _EXPECT)]
    assert keys == [key]


def test_verdict_on_a_mesh_allows_the_unprobed_cross_gate_only():
    dbg, diag = _good()
    diag["system"]["devices"] = [
        {"id": i, "platform": "tpu", "bytesInUse": 1000 + i} for i in range(4)
    ]
    dbg["kernels"]["gram_gates"]["cross"] = {"ok": None, "fails": 0}
    assert chip_smoke.verdict(dbg, diag, _EXPECT) == []
    assert chip_smoke.placement_failures(diag) == []
    dbg["kernels"]["gram_gates"]["self"] = {"ok": None, "fails": 0}
    assert [k for k, _ in chip_smoke.verdict(dbg, diag, _EXPECT)] == ["gate.self"]
    # device 0 carrying the whole ingest
    diag["system"]["devices"][0]["bytesInUse"] = 9000
    assert [k for k, _ in chip_smoke.placement_failures(diag)] == ["placement"]


# -- no stand-in for the device at process start -----------------------------


def test_cli_server_fails_when_its_backend_cannot_initialise(tmp_path):
    p = subprocess.run(
        [sys.executable, "-m", "pilosa_tpu.cli", "server", "-d",
         str(tmp_path / "d"), "--bind", f"127.0.0.1:{chip_smoke.free_port()}"],
        env=_child_env(JAX_PLATFORMS="nosuchbackend"), cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    out = p.stdout + p.stderr
    assert "failed to initialise" in out
    assert "CPU backend" not in out and "listening" not in out


# -- a compile cache that can be placed --------------------------------------

_PRINT_CACHE = (
    "import jax; from pilosa_tpu import jaxcache; p = jaxcache.configure();"
    "print(p); print(jax.config.jax_compilation_cache_dir);"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)"
)


def _cache_lines(cwd, **extra):
    env = _child_env(**extra)
    if "JAX_COMPILATION_CACHE_DIR" not in extra:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "-c", _PRINT_CACHE], env=env, cwd=str(cwd),
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.split()


def test_cache_is_one_fixed_path_in_the_checkout(tmp_path):
    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    one = _cache_lines(tmp_path / "a")
    two = _cache_lines(tmp_path / "b")
    want = os.path.join(REPO, ".jax_cache")
    assert one == two == [want, want, "0.0"]


def test_cache_dir_is_left_to_the_environment(tmp_path, monkeypatch):
    placed = str(tmp_path / "placed")
    assert _cache_lines(tmp_path, JAX_COMPILATION_CACHE_DIR=placed) == [
        placed, placed, "0.0",
    ]
    # ... and no directory is set in code then
    import jax

    from pilosa_tpu import jaxcache

    calls = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append(k))
    assert jaxcache.configure() == placed
    assert "jax_compilation_cache_dir" not in calls
    assert "jax_persistent_cache_min_compile_time_secs" in calls


def test_devledger_books_cache_retrievals_apart():
    """jax announces a persistent-cache hit just before the
    backend_compile_duration that closes the same request; the ledger
    must count it as a retrieval, not a compile."""
    from pilosa_tpu.obs import devledger

    led = devledger.ledger()
    site = devledger.site("test.chip_smoke")
    before = devledger.counters()
    with site.launch(sig="retrieved"):
        led._on_plain_event("/jax/compilation_cache/cache_hits")
        led._on_event("/jax/core/compile/backend_compile_duration", 0.01)
    with site.launch(sig="compiled"):
        led._on_event("/jax/core/compile/backend_compile_duration", 0.5)
    after = devledger.counters()
    assert after["persistentCacheHits"] - before["persistentCacheHits"] == 1
    assert after["compiles"] - before["compiles"] == 1
