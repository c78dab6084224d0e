"""``serve_child`` with the timed path broken underneath: every
import-roaring request is acknowledged at once and applied only when the
next one for the same field arrives, so an acknowledged import is not
visible to the reads that follow it.  Used by test_ingest only."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import serve_child  # noqa: E402

sys.path.insert(0, serve_child.REPO)

from pilosa_tpu.server import api  # noqa: E402

_sound = api.API.import_roaring
_held: dict = {}


def _one_late(self, index, field, shard, data, **kw):
    if kw.get("remote"):
        return _sound(self, index, field, shard, data, **kw)
    held = _held.get((index, field))
    _held[(index, field)] = (shard, data, kw)
    if held is not None:
        _sound(self, index, field, held[0], held[1], **held[2])
    return {"changed": 0}


api.API.import_roaring = _one_late

if __name__ == "__main__":
    sys.exit(serve_child.main())
