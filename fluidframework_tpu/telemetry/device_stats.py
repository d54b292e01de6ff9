"""Device-resident telemetry planes: the host-side layout + fold.

The serving hot path runs inside single device programs (the serving
megakernel's scanned windows, paged applies on gathered page views,
batched extract epochs), so the facts that matter — per-window op mix,
noop-skipped applies, overflow flags, rows reclaimed by zamboni, lane
fill — are invisible to host spans unless a readback pays for them.
The device programs therefore emit a compact int32 stats plane IN THEIR
EXISTING readback (serve_step.py packs it into the flat16 narrow
result as lo/hi int16 halves; the paged/extract kernels return it next
to the planes they already return), and this module is the single
source of truth for the slot layout plus the host-side fold into the
counters/histogram/Prometheus surface.

Contracts (gated by ``make obs-smoke`` + tests/test_device_stats.py):

  * bit-identity-neutral — telemetry on/off produces the identical
    emit stream and lane planes (the plane is an appended output, never
    an input to the op phases);
  * zero extra dispatches and zero extra host round-trips per
    window/ring (the plane rides the flat16 the host already fetches);
  * device-counted op totals reconcile EXACTLY with host-side counts —
    every fold takes the device vector AND a host-derived mirror, and
    both land as counters (``device.serving.*`` vs ``host.serving.*``)
    so the reconciliation is a live operational check, not a test-only
    artifact.

The process-wide toggle is static at dispatch (a different compiled
program with the stats tail present/absent), so flipping it costs one
recompile, never a semantic change.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Sequence

from . import counters as _counters

# -- slot layouts ------------------------------------------------------------
# Index order is THE contract between the traced device code
# (serve_step._serve_window_impl, kernel.apply_ops_paged and friends)
# and the host decode: append-only, never reorder.

# One serving window (rides the flat16 narrow result as 2*N int16).
SERVE_SLOTS = (
    "ops_insert",          # admitted merge ops by kind (post nack/void)
    "ops_remove",
    "ops_annotate",
    "ops_ack_insert",
    "ops_ack_remove",
    "ops_insert_run",
    "lww_ops",             # admitted LWW ops (any kind)
    "ticket_admitted",     # sequenced messages (ops + joins + system)
    "ticket_nacked",
    "ticket_not_joined",
    "merge_overflow_lanes",
    "lww_overflow_lanes",
    "noop_skipped_applies",  # ring padding skips (all-NOOP op planes)
    "merge_rows_live",     # post-window page-group fill (sum of counts)
    "lww_keys_live",
)
N_SERVE = len(SERVE_SLOTS)

# One paged apply / paged-burst chunk.
PAGED_SLOTS = (
    "ops_insert",
    "ops_remove",
    "ops_annotate",
    "ops_ack_insert",
    "ops_ack_remove",
    "ops_insert_run",
    "overflow_docs",
    "rows_live",           # post-apply live rows across the group
)
N_PAGED = len(PAGED_SLOTS)

# One fused zamboni+extract dispatch over a page group.
EXTRACT_SLOTS = (
    "docs",
    "rows_live",           # post-compaction live rows
    "rows_reclaimed",      # zamboni reclaim: pre minus post live rows
)
N_EXTRACT = len(EXTRACT_SLOTS)

# Slots folded as monotone counters; the rest are point-in-time gauges.
_SERVE_GAUGES = {"merge_rows_live", "lww_keys_live"}

# -- process-wide toggle -----------------------------------------------------

_lock = threading.Lock()
_enabled = os.environ.get("FLUID_DEVICE_STATS", "1") not in ("0", "false")


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    """Flip the process-wide toggle (static at dispatch: the next
    window compiles with/without the stats tail; results are
    bit-identical either way)."""
    global _enabled
    with _lock:
        _enabled = bool(on)


# -- folds -------------------------------------------------------------------

def _fold(prefix: str, slots: Sequence[str], vec, gauges=frozenset()):
    for name, value in zip(slots, vec):
        v = float(value)
        if name in gauges:
            _counters.gauge(f"{prefix}.{name}", v)
        elif v:
            _counters.increment(f"{prefix}.{name}", v)


def fold_serve(device_vec, host_vec=None) -> None:
    """Fold one window's device stats vector (int order = SERVE_SLOTS)
    into ``device.serving.*``; ``host_vec`` — the host-derived mirror
    computed from the same window's staging + decoded ticket results —
    lands as ``host.serving.*`` so device-vs-host reconciliation is a
    counter diff."""
    _fold("device.serving", SERVE_SLOTS, device_vec, _SERVE_GAUGES)
    if host_vec is not None:
        _fold("host.serving", SERVE_SLOTS, host_vec, _SERVE_GAUGES)
        for name, d, h in zip(SERVE_SLOTS, device_vec, host_vec):
            if name not in _SERVE_GAUGES and int(d) != int(h):
                _counters.increment("device.serving.reconcile_mismatch")
                break


def fold_paged(device_vec, host_vec=None) -> None:
    _fold("device.paged", PAGED_SLOTS, device_vec)
    if host_vec is not None:
        _fold("host.paged", PAGED_SLOTS, host_vec)
        for name, d, h in zip(PAGED_SLOTS, device_vec, host_vec):
            if name != "rows_live" and int(d) != int(h):
                _counters.increment("device.paged.reconcile_mismatch")
                break


def fold_extract(device_vec) -> None:
    _fold("device.extract", EXTRACT_SLOTS, device_vec)


# -- flush-span enrichment ---------------------------------------------------
# The serving.flush span gains device-measured sub-facts: the sequencer
# snapshots these keys at flush start and stamps the deltas at flush
# end (windows retired during the flush — including deferred windows
# from earlier flushes draining now — attribute here).

_FLUSH_KEYS = (
    ("dev_ops", ("device.serving.ops_insert",
                 "device.serving.ops_remove",
                 "device.serving.ops_annotate",
                 "device.serving.ops_ack_insert",
                 "device.serving.ops_ack_remove",
                 "device.serving.ops_insert_run",
                 "device.serving.lww_ops")),
    ("dev_admitted", ("device.serving.ticket_admitted",)),
    ("dev_nacked", ("device.serving.ticket_nacked",)),
    ("dev_overflow_lanes", ("device.serving.merge_overflow_lanes",
                            "device.serving.lww_overflow_lanes")),
    ("dev_noop_skips", ("device.serving.noop_skipped_applies",)),
    ("dev_zamboni_rows", ("device.extract.rows_reclaimed",
                          "zamboni.rows_reclaimed")),
)


def begin_flush() -> tuple:
    return tuple(sum(_counters.get(c) for c in cs)
                 for _, cs in _FLUSH_KEYS)


def flush_facts(token: tuple) -> Dict[str, int]:
    """Non-zero deltas since ``begin_flush`` — the serving.flush span's
    device-measured attributes."""
    out: Dict[str, int] = {}
    for (name, cs), before in zip(_FLUSH_KEYS, token):
        delta = sum(_counters.get(c) for c in cs) - before
        if delta:
            out[name] = int(delta)
    return out


def snapshot() -> Dict[str, float]:
    """Every device.*/host.* stats counter — the /health block."""
    return {k: v for k, v in _counters.snapshot().items()
            if k.startswith(("device.", "host."))}


def reconcile() -> Optional[dict]:
    """Device-vs-host totals for the countable serving slots: {slot:
    (device, host)} for any slot that disagrees, or None when exact."""
    snap = _counters.snapshot()
    bad = {}
    for name in SERVE_SLOTS:
        if name in _SERVE_GAUGES:
            continue
        d = snap.get(f"device.serving.{name}", 0.0)
        h = snap.get(f"host.serving.{name}", 0.0)
        if int(d) != int(h):
            bad[name] = (int(d), int(h))
    return bad or None
