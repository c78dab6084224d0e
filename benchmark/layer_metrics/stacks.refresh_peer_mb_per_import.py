"""Bytes a stack's refresh after a write moved from one chip to another, in
MB an import request acknowledged in the window: delta
``serving_cache.stack_refresh_peer_bytes`` of ``/debug/vars``
(pilosa_tpu/exec/stacks.py ``Stacks._refresh``, the ``peer`` route: a block
gathered on the chip that has the fragment's copy and sent to the one chip
that keeps the shard's slice of the stack) x 1e-6 over ``window.imports``.
0 is its good reading: the fragment's copy and the stack's slice lie on one
chip (``parallel/mesh.py`` ``chip_of_shard``), as for every shard of a list
without gaps.

Reads 0 on a program without the counter (see ``listener.ms_per_read.py``),
and where no import was acknowledged."""


def read(ctx: dict) -> float:
    moved = ctx["vars"].get("serving_cache", {}).get("stack_refresh_peer_bytes")
    imports = ctx["window"].get("imports")
    if moved is None or not imports:
        return 0.0
    return 1e-6 * moved / imports
