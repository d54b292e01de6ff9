"""Cost-model routing for single-lane bulk catch-up: scalar vs device.

The device kernel wins by BATCH parallelism (the server's B-lane windows)
and by replacing the scalar path's O(live-segments) per-op position walk
with vectorized passes. A client catch-up is B=1, so the kernel's only
lever is the per-segment term — and dispatch overhead is paid per chunk:

- CPU backend, measured on this host (2026-07-31, tails 64..4096 over
  docs of 50..3000 live segments): the XLA kernel at B=1 NEVER beats the
  scalar oracle — bulk/scalar time ratios 0.09..0.68, improving with doc
  size but not crossing 1. Routing therefore always picks scalar on CPU.
- TPU v5e, measured on the chip by chip_smoke.py's loader phase (PR 21):
  one B=1 catch-up chunk dispatch (kernel apply + the host overflow
  fetch, at the 4096-row capacity a 1k-segment doc's tail runs at) costs
  1.87 ms at T=1, and each further op of a 512-op chunk 67 us. The
  crossover comes from the scalar per-op cost growing with live
  segments while the kernel's stays flat (routing stays scalar near the
  line: 1.2x hysteresis).

Reference behavior being routed: deltaManager.ts:1401 catchUp applies
the fetched tail; the reference has one path, this framework has two and
must never pick the slower one (round-4 verdict: the flat 64-op
threshold made CPU single-doc replay 4x slower than scalar).

Override: FLUID_TPU_FORCE_BULK=1 forces the device path (tests exercise
kernel correctness regardless of backend), =0 forces scalar.
"""

from __future__ import annotations

import os

# Scalar per-op cost ~= SCALAR_BASE_S + SCALAR_PER_SEG_S * live_segments
# (linear fit of the host measurements above: ~26us at 50 segs, ~170us at
# 500, ~1.1ms at 3000).
SCALAR_BASE_S = 20e-6
SCALAR_PER_SEG_S = 0.35e-6

# Device path ~= per-chunk dispatch cost + flat per-op kernel step, both
# measured on a TPU v5e (chip_smoke.py loader phase, PR 21; PERF.md).
TPU_DISPATCH_S = 1.87e-3
TPU_PER_OP_S = 67e-6
HYSTERESIS = 1.2        # prefer scalar near the line (misroute is cheap
#                         scalar-side, expensive device-side)


def device_bulk_wins(tail_len: int, live_segments: int,
                     backend: str | None = None) -> bool:
    """Should this single-lane tail ride the device kernel?

    backend defaults to the active jax backend; pass it explicitly in
    tests to keep the model a pure function."""
    force = os.environ.get("FLUID_TPU_FORCE_BULK")
    if force == "1":
        return True
    if force == "0":
        return False
    if backend is None:
        import jax
        backend = jax.default_backend()
    if backend != "tpu":
        # Measured: the B=1 kernel never beats the scalar oracle on CPU.
        return False
    from .catchup import CHUNK_T
    scalar_s = tail_len * (SCALAR_BASE_S
                           + SCALAR_PER_SEG_S * live_segments)
    chunks = -(-tail_len // CHUNK_T)
    device_s = chunks * TPU_DISPATCH_S + tail_len * TPU_PER_OP_S
    return scalar_s > device_s * HYSTERESIS
