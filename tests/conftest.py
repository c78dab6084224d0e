"""Test configuration.

Runs the whole suite on a virtual 8-device CPU mesh (multi-chip sharding is
validated without TPU hardware, mirroring how the reference boots real
in-process multi-node clusters in tests — reference test/pilosa.go:344-400)
and with a small shard width (2^14) so fragment tensors stay tiny, the way
the reference selects SHARD_WIDTH via build tags (reference Makefile:9,
shardwidth/16.go).

The platform is forced through ``JAX_PLATFORMS`` before jax is imported
(conftest runs at collection time, before test modules import
jax-dependent code), so the suite never takes a chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH", "14")

# Runtime lockdep witness: every project lock allocated from here on is
# wrapped, so the whole tier-1 run doubles as a lock-order race probe
# (docs/robustness.md "Concurrency discipline").  Installed before test
# modules import pilosa_tpu code so module-level locks get wrapped too.
# Mode comes from PILOSA_LOCKWITNESS (raise | log | off), default raise.
from pilosa_tpu.testing import lockwitness  # noqa: E402

lockwitness.install()

# Spans open only under names of the span table (pilosa_tpu/obs/tracing.py);
# these are the names tests open spans of their own under.
from pilosa_tpu.obs import tracing  # noqa: E402

tracing.register_family(
    "test.", ("op", "parent", "child", "other", "root", "query"), "tests"
)


def pytest_terminal_summary(terminalreporter):
    bad = lockwitness.findings()
    if bad:
        terminalreporter.section("lock order inversions (lockwitness)")
        for inv in bad:
            terminalreporter.line(
                f"{inv['locks'][0]} <-> {inv['locks'][1]} "
                f"[{inv['thread']}]: {inv['this_order']}; "
                f"prior: {inv['prior_order']}"
            )


def pytest_sessionfinish(session, exitstatus):
    # In raise mode an inversion already failed its test; this catches
    # log mode and exceptions swallowed inside worker threads.
    if lockwitness.findings() and session.exitstatus == 0:
        session.exitstatus = 1
