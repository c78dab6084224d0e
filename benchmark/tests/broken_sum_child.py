"""``serve_child`` with the timed path broken underneath for a mix that only
sums and groups: every ``Sum`` the program produces comes out one too high,
and the first group of every ``GroupBy`` counts one more.  Used by
test_ssb only."""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import serve_child  # noqa: E402

sys.path.insert(0, serve_child.REPO)

from pilosa_tpu.exec.result import GroupCount, ValCount  # noqa: E402
from pilosa_tpu.server import api  # noqa: E402

_sound = api.API._execute_query


def _altered(r):
    if isinstance(r, ValCount):
        return dataclasses.replace(r, value=r.value + 1)
    if isinstance(r, list) and r and isinstance(r[0], GroupCount):
        return [dataclasses.replace(r[0], count=r[0].count + 1)] + list(r[1:])
    return r


def _one_too_high(self, index, pql_text, shards):
    return [_altered(r) for r in _sound(self, index, pql_text, shards)]


api.API._execute_query = _one_too_high

if __name__ == "__main__":
    sys.exit(serve_child.main())
