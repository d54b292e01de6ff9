"""Roofline shares of single device programs: the bytes a program must
move, counted by the program itself, over what the chip's HBM moves at
its published peak (peaks.py) in the device time the trace gives that
program.

``lww_apply_share``: the LWW apply kernel (server/lww_kernel.py
``apply_lww_batched``). Its bytes are the window's delta of the program
counter ``lww.apply.bytes``: per dispatch, one read and one write of each
dispatched lane's slot table (key, val and seq as int32: lanes x capacity
x 12 B x 2) plus the five int32 op columns (lanes x steps x 20 B). Its
time is the device seconds of the ``jit_apply_lww_batched`` programs
among the trace's ``device_ops``.
"""

LWW_APPLY_PROGRAM = "jit_apply_lww_batched"
LWW_APPLY_BYTES = "lww.apply.bytes"


def program_seconds(trace, prefix: str):
    """Device seconds of the programs named ``prefix...`` among the
    trace's top programs; None where none is listed."""
    if not trace:
        return None
    found = [s for name, s in trace["device_ops"] if name.startswith(prefix)]
    return sum(found) if found else None


def counter_delta(run, name: str):
    """A program counter's growth over the window; None where the run
    holds no counter snapshots or the program has no such counter."""
    before = getattr(run, "counters0", None)
    after = getattr(run, "counters1", None)
    if not before or not after or name not in after:
        return None
    return after[name] - before.get(name, 0.0)


def hbm_share(moved: float, seconds: float, bytes_per_s: float) -> float:
    return moved / (seconds * bytes_per_s)


def lww_apply_share(run, bytes_per_s: float = None):
    """The LWW apply's share of the HBM roofline over the window; None
    where the trace lists no such program or the program counts no
    bytes. Without ``bytes_per_s`` the peak of the device JAX finds."""
    seconds = program_seconds(run.trace, LWW_APPLY_PROGRAM)
    moved = counter_delta(run, LWW_APPLY_BYTES)
    if not seconds or not moved:
        return None
    if bytes_per_s is None:
        import jax

        from .peaks import peaks

        bytes_per_s = peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return hbm_share(moved, seconds, bytes_per_s)
