"""Share of the window's launches whose operands lay on more than one
device, each one SPMD program over the serving mesh: delta
``devledger.totals.meshLaunches`` over delta ``devledger.totals.launches`` of
``/debug/vars``, in %.  Near 100 the cell is the mesh's; what is left are
launches on one device (a fragment's own copy, a host-tier kernel).

Reads 0 on a program whose ledger does not count mesh launches yet (see
``listener.ms_per_read.py``), and where nothing was launched."""


def read(ctx: dict) -> float:
    totals = ctx["vars"]["devledger"]["totals"]
    if "meshLaunches" not in totals or not totals["launches"]:
        return 0.0
    return 100.0 * totals["meshLaunches"] / totals["launches"]
