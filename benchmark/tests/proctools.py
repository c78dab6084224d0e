"""What is alive, read from ``/proc``.  A process that has ended and waits
to be reaped (state ``Z``) is not alive."""

import os
import time


def _read(pid: str, name: str) -> bytes:
    try:
        with open(f"/proc/{pid}/{name}", "rb") as f:
            return f.read()
    except OSError:  # gone between the listing and the read, or another user's
        return b""


def _state_and_parent(pid: str) -> tuple[str, int]:
    stat = _read(pid, "stat").decode("utf-8", "replace")
    fields = stat[stat.rfind(")") + 2:].split()  # the command, in brackets, may hold spaces
    return (fields[0], int(fields[1])) if len(fields) > 1 else ("Z", 0)


def _alive(keep) -> list[str]:
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        state, parent = _state_and_parent(pid)
        if state != "Z" and int(pid) != os.getpid() and keep(pid, parent):
            out.append(f"{pid}: " + _read(pid, "cmdline").replace(b"\0", b" ").decode("utf-8", "replace"))
    return out


def alive_with(marker: str) -> list[str]:
    """The live processes whose command line or starting environment holds
    ``marker``: a run started with ``TMPDIR=<marker>`` hands it to every
    process it starts, and to theirs."""
    m = marker.encode()
    return _alive(lambda pid, parent: m in _read(pid, "cmdline") or m in _read(pid, "environ"))


def children_of(parent_pid: int) -> list[str]:
    return _alive(lambda pid, parent: parent == parent_pid)


def left_after(seconds: float, marker: str) -> list[str]:
    """``alive_with(marker)`` once it is empty, or after ``seconds`` at the latest."""
    end = time.monotonic() + seconds
    while True:
        left = alive_with(marker)
        if not left or time.monotonic() >= end:
            return left
        time.sleep(0.1)
