"""On-chip kernel conformance: fused Pallas apply vs scan kernel,
bit-identity on the REAL device.

The test suite proves fused==scan under the Pallas interpreter on CPU
(tests/test_pallas_apply.py); this tool re-proves it on actual TPU
hardware, where Mosaic lowering — not the interpreter — executes the
kernel. Run before trusting a new chip/toolchain/jax version:

    python -m fluidframework_tpu.tools.tpu_conformance          # quick
    python -m fluidframework_tpu.tools.tpu_conformance --heavy  # +cap-1024

Exits nonzero on any mismatch. Timing uses jitted chained reps (eager
dispatch pays a fixed host cost per call)."""

from __future__ import annotations

import argparse
import random
import sys
import time


def _traces(b: int, t: int, seed: int, removes: bool = True):
    from fluidframework_tpu.mergetree.oppack import HostOp, OpKind

    rng = random.Random(seed)
    out = []
    for d in range(b):
        ops, length, seq = [], 0, 0
        for i in range(t):
            seq += 1
            if removes and length > 4 and rng.random() < 0.25:
                a = rng.randrange(length - 2)
                width = rng.randrange(1, 3)
                ops.append(HostOp(kind=OpKind.REMOVE, seq=seq,
                                  ref_seq=seq - 1, client=d % 3,
                                  pos1=a, pos2=a + width, op_id=i))
                length -= width
                continue
            n = rng.randrange(1, 4)
            ops.append(HostOp(kind=OpKind.INSERT, seq=seq, ref_seq=seq - 1,
                              client=d % 3, pos1=rng.randrange(length + 1),
                              op_id=i, new_len=n))
            length += n
        out.append(ops)
    return out


def check(b: int, t: int, cap: int, seed: int) -> bool:
    import jax
    import numpy as np

    from fluidframework_tpu.mergetree import kernel
    from fluidframework_tpu.mergetree.oppack import pack_ops
    from fluidframework_tpu.mergetree.pallas_apply import (
        apply_ops_fused_pallas, tile_for_capacity)
    from fluidframework_tpu.mergetree.state import make_state

    packed = jax.device_put(pack_ops(_traces(b, t, seed)))
    scan_j = jax.jit(lambda s, o: kernel.apply_ops_batched_keep(s, o))
    # fluidlint: disable=MISSING_DONATE — conformance re-runs both kernels
    # over the SAME inputs to diff outputs; donation would corrupt the ref.
    fused_j = jax.jit(apply_ops_fused_pallas)

    results = {}
    for name, fn in (("scan", scan_j), ("fused", fused_j)):
        st = jax.device_put(make_state(cap, 2, batch=b))
        out = fn(st, packed)
        jax.device_get(out.count)  # full completion
        t0 = time.perf_counter()
        chained = fn(jax.device_put(make_state(cap, 2, batch=b)), packed)
        for _ in range(2):
            chained = fn(chained._replace(overflow=out.overflow), packed)
        jax.device_get(chained.count)
        results[name] = (out, (time.perf_counter() - t0) / 3)

    ref, scan_dt = results["scan"]
    got, fused_dt = results["fused"]
    ok = True
    for f in ref._fields:
        a, c = np.asarray(jax.device_get(getattr(ref, f))), \
            np.asarray(jax.device_get(getattr(got, f)))
        if not (a == c).all():
            print(f"  MISMATCH in {f} (b={b} t={t} cap={cap} seed={seed})")
            ok = False
    tile = tile_for_capacity(cap)
    print(f"  b={b} t={t} cap={cap} tile={tile}: "
          f"{'OK' if ok else 'FAIL'}  scan {scan_dt*1e3:.1f}ms "
          f"fused {fused_dt*1e3:.1f}ms")
    return ok


def check_runs(b: int, t_ops: int, cap: int, seed: int) -> bool:
    """INSERT_RUN Mosaic conformance: pack typing-burst streams and
    compare the fused runs variant against the scan kernel WITH the same
    RunCols — the packed apply itself differential-checked on chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fluidframework_tpu.mergetree import kernel
    from fluidframework_tpu.mergetree.oppack import (HostOp, OpKind,
                                                     RunCols,
                                                     pack_run_slots,
                                                     pack_slots)
    from fluidframework_tpu.mergetree.pallas_apply import (
        apply_ops_fused_pallas)
    from fluidframework_tpu.mergetree.state import make_state

    rng = random.Random(seed)
    docs = []
    for d in range(b):
        ops, length, seq = [], 0, 0
        while len(ops) < t_ops:
            if rng.random() < 0.7:  # typing burst, frozen ref
                ref = seq
                pos = rng.randrange(length + 1) if length else 0
                for _ in range(rng.randrange(3, 12)):
                    seq += 1
                    ops.append(HostOp(kind=OpKind.INSERT, seq=seq,
                                      ref_seq=ref, client=1, pos1=pos,
                                      op_id=len(ops), new_len=1))
                    pos += 1
                    length += 1
            elif length > 4:
                seq += 1
                a = rng.randrange(length - 2)
                ops.append(HostOp(kind=OpKind.REMOVE, seq=seq,
                                  ref_seq=seq - 1, client=1, pos1=a,
                                  pos2=a + 1, op_id=len(ops)))
                length -= 1
            else:
                seq += 1
                ops.append(HostOp(kind=OpKind.INSERT, seq=seq,
                                  ref_seq=seq - 1, client=1, pos1=0,
                                  op_id=len(ops), new_len=2))
                length += 2
        docs.append(pack_run_slots(ops[:t_ops], base_seq=0))
    t_slots = max(len(s) for s in docs)
    packed_l, runs_l = zip(*(pack_slots(s, steps=t_slots) for s in docs))
    packed = type(packed_l[0])(*[
        jnp.stack([getattr(p, f) for p in packed_l])
        for f in packed_l[0]._fields])
    runs = RunCols(*[jnp.stack([getattr(r, f) for r in runs_l])
                     for f in RunCols._fields])
    packed, runs = jax.device_put((packed, runs))

    out_scan = kernel._scan_ops(jax.device_put(make_state(cap, 2, batch=b)),
                                packed, batched=True, runs=runs)
    out_fused = apply_ops_fused_pallas(
        jax.device_put(make_state(cap, 2, batch=b)), packed, runs=runs)
    ok = True
    for f in out_scan._fields:
        a = np.asarray(jax.device_get(getattr(out_scan, f)))
        c = np.asarray(jax.device_get(getattr(out_fused, f)))
        if not (a == c).all():
            print(f"  RUNS MISMATCH in {f} (b={b} t={t_ops} cap={cap} "
                  f"seed={seed})")
            ok = False
    print(f"  runs b={b} t={t_ops} cap={cap}: {'OK' if ok else 'FAIL'}")
    return ok


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--heavy", action="store_true")
    args = parser.parse_args()

    import jax
    backend = jax.default_backend()
    print(f"backend: {backend}")
    if backend != "tpu":
        print("no TPU — run tests/test_pallas_apply.py for the "
              "interpreter conformance instead")
        return 2

    shapes = [(512, 64, 256, 0), (2048, 100, 256, 1), (128, 48, 512, 2)]
    if args.heavy:
        shapes.append((512, 128, 1024, 3))   # narrow-tile 3-D op path
    results = [check(*s) for s in shapes]  # run EVERY shape
    # INSERT_RUN Mosaic variant (round 4).
    results.append(check_runs(256, 64, 256, 7))
    if args.heavy:
        results.append(check_runs(512, 96, 512, 8))
    results.append(check_fused_sp(64, 48, 256, 11))
    ok = all(results)
    print("CONFORMANCE", "OK" if ok else "FAILED")
    return 0 if ok else 1


def check_fused_sp(b: int, t: int, cap: int, seed: int) -> bool:
    """Round-5: the fused×sp GSPMD body (mergetree/fused_sp.py —
    two-level reshape prefix sums) lowers through real XLA:TPU, not the
    interpreter; a single chip executes the sp>1 formulation with the
    collectives degenerating, so this validates the LOWERING now and the
    multi-chip placement stays covered by dryrun_multichip."""
    import numpy as np

    from fluidframework_tpu.mergetree import fused_sp, kernel
    from fluidframework_tpu.mergetree.oppack import pack_ops
    from fluidframework_tpu.mergetree.state import make_state

    packed = pack_ops(_traces(b, t, seed))
    ref = kernel.apply_ops_batched_keep(make_state(cap, 2, batch=b),
                                        packed)
    out = fused_sp.apply_ops_fused_sp(make_state(cap, 2, batch=b),
                                      packed, 4)
    ok = all(
        bool(np.array_equal(np.asarray(getattr(ref, f)),
                            np.asarray(getattr(out, f))))
        for f in ref._fields)
    print(f"fused_sp b={b} t={t} cap={cap} sp=4: "
          f"{'OK' if ok else 'MISMATCH'}")
    return ok


if __name__ == "__main__":
    sys.exit(main())
