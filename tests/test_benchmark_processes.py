"""The harness's process guard in tier-1: two of ``benchmark/tests/
test_processes.py``'s cases as they are (no JAX, a few seconds each) — a
``run.py`` that is killed takes a server child that never serves with it, and a
run whose server never serves ends itself at its limit and leaves nothing."""

import os
import sys

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
sys.path[:0] = [BENCH, os.path.join(BENCH, "tests")]

from test_processes import (  # noqa: E402,F401
    started,
    test_limit_ends_a_run_whose_server_never_serves,
    test_sigkill_takes_a_child_that_never_serves,
)
