"""``serve_child`` with the timed path broken underneath: every Count the
program produces comes out one too high.  Used by test_broken_path only."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import serve_child  # noqa: E402

sys.path.insert(0, serve_child.REPO)

from pilosa_tpu.server import api  # noqa: E402

_sound = api.API._execute_query


def _one_too_high(self, index, pql_text, shards):
    results = _sound(self, index, pql_text, shards)
    return [r + 1 if isinstance(r, int) and not isinstance(r, bool) else r for r in results]


api.API._execute_query = _one_too_high

if __name__ == "__main__":
    sys.exit(serve_child.main())
