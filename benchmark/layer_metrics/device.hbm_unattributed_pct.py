"""Share of the device memory in use that no owner of the program's budget
accounts for, at the window's start: 100 x (``bytesInUse`` - sum of
``byOwner``) / ``bytesInUse`` of ``/debug/vars`` ``device``.  ``byOwner`` is what
the budget holds per owner kind (serving stacks by field type, fragment
copies) over all local devices, so it is divided by their number;
``bytesInUse`` is the backend's own figure for the fullest of them.

Where the backend keeps no memory statistics the reader raises on ``tpu`` and
reads 0 on the CPU of a rehearsal, which can never print a passing line and
whose ``memory_peak_bytes`` is null for the same reason.  It reads 0 as well
on a program whose ``device`` block has no ``byOwner`` yet (see
``listener.ms_per_read.py``)."""


def read(ctx: dict) -> float:
    dev = ctx["vars_start"]["device"]
    if "byOwner" not in dev:
        return 0.0
    in_use = dev["bytesInUse"]
    if in_use is None:
        if dev["platform"] == "tpu":
            raise KeyError("the TPU backend reported no memory statistics")
        return 0.0
    held = sum(dev["byOwner"].values()) / dev["devices"]
    return 100.0 * (in_use - held) / in_use if in_use else 0.0
