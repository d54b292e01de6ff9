"""Fused Pallas apply (mergetree/pallas_apply.py) conformance: the
VMEM-resident whole-stream kernel must be bit-identical to the scan×vmap
kernel (which itself is conformance-locked to the scalar oracle in
tests/test_kernel.py). Runs the jnp reference everywhere and the Pallas
interpreter path for the kernel body itself."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.testing.traces import gen_traces
from fluidframework_tpu.mergetree import kernel, pallas_apply
from fluidframework_tpu.mergetree.host import OpBuilder
from fluidframework_tpu.mergetree.oppack import PackedOps, pack_ops
from fluidframework_tpu.mergetree.state import make_state

from test_kernel import build_kernel_ops, random_schedule

_CHECK = ("length", "ins_seq", "ins_client", "local_seq", "rem_seq",
          "rem_local_seq", "rem_clients", "origin_op", "origin_off",
          "anno", "count", "min_seq", "seq", "overflow")


def assert_states_equal(a, b):
    for name in _CHECK:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=name)


def _batched_from_traces(b, t, cap, seed):
    cols = gen_traces(b, t, seed=seed)
    ops = PackedOps(**{f: jnp.asarray(cols[f]) for f in PackedOps._fields})
    return make_state(cap, 2, batch=b), ops


class TestFusedRefConformance:
    @pytest.mark.parametrize("seed,b,t,cap", [(0, 16, 32, 64),
                                              (1, 8, 64, 128),
                                              (2, 32, 16, 64)])
    def test_trace_batches_match_scan_kernel(self, seed, b, t, cap):
        st, ops = _batched_from_traces(b, t, cap, seed)
        ref = kernel.apply_ops_batched_keep(st, ops)
        fused = pallas_apply.apply_ops_fused_ref(
            *_batched_from_traces(b, t, cap, seed))
        assert_states_equal(ref, fused)

    @pytest.mark.parametrize("seed", range(6))
    def test_rich_schedules_match(self, seed):
        """Annotates (ring + LWW), overlapping removes, concurrent inserts:
        the random sequenced schedule generator from test_kernel."""
        rng = random.Random(seed + 500)
        tuples = random_schedule(rng, n_clients=4, n_ops=40)
        builder = OpBuilder()
        host_ops = build_kernel_ops(builder, tuples)
        packed = pack_ops([host_ops, host_ops[: len(host_ops) // 2]])
        st = make_state(256, 8, batch=2)
        ref = kernel.apply_ops_batched_keep(st, packed)
        fused = pallas_apply.apply_ops_fused_ref(
            make_state(256, 8, batch=2), packed)
        assert_states_equal(ref, fused)

    def test_overflow_flag_matches(self):
        st, ops = _batched_from_traces(4, 40, 16, 3)  # tiny capacity
        ref = kernel.apply_ops_batched_keep(st, ops)
        fused = pallas_apply.apply_ops_fused_ref(
            *_batched_from_traces(4, 40, 16, 3))
        np.testing.assert_array_equal(np.asarray(ref.overflow),
                                      np.asarray(fused.overflow))
        assert bool(np.asarray(ref.overflow).any())


class TestFusedPallasInterpret:
    @pytest.mark.parametrize("seed", [0, 1])
    def test_interpret_matches_scan_kernel(self, seed):
        st, ops = _batched_from_traces(8, 20, 64, seed)
        ref = kernel.apply_ops_batched_keep(st, ops)
        fused = pallas_apply.apply_ops_fused_pallas(
            *_batched_from_traces(8, 20, 64, seed), interpret=True)
        assert_states_equal(ref, fused)

    def test_narrow_tile_3d_op_path_matches(self):
        """Capacity above 512 shrinks the doc tile below 128, switching
        the op columns to the 3D block layout — conformance for that
        lowering path (tile_for_capacity(1024) == 64)."""
        assert pallas_apply.tile_for_capacity(512) == 128
        assert pallas_apply.tile_for_capacity(1024) == 64
        assert pallas_apply.tile_for_capacity(8192) == 8
        st, ops = _batched_from_traces(4, 30, 1024, 7)
        ref = kernel.apply_ops_batched_keep(st, ops)
        fused = pallas_apply.apply_ops_fused_pallas(
            *_batched_from_traces(4, 30, 1024, 7), interpret=True)
        assert_states_equal(ref, fused)


class TestFusedAnnotateRing:
    def test_annotate_ring_overflow_matches(self):
        """Annotate-heavy schedule at ring depth 1: overflow flags must
        match the scan kernel exactly (correct-by-flag discipline)."""
        rng = random.Random(77)
        tuples = random_schedule(rng, n_clients=3, n_ops=60)
        # Bias to annotates: rewrite half the removes into annotates.
        builder = OpBuilder()
        host_ops = build_kernel_ops(builder, tuples)
        packed = pack_ops([host_ops])
        ref = kernel.apply_ops_batched_keep(
            make_state(256, 1, batch=1), packed)
        fused = pallas_apply.apply_ops_fused_ref(
            make_state(256, 1, batch=1), packed)
        assert_states_equal(ref, fused)


class TestFusedInsertRun:
    def test_interpret_run_variant_matches_scan_with_runs(self):
        """The Mosaic INSERT_RUN variant (fused kernel + run sub-columns)
        is bit-identical to the scan kernel with the same RunCols."""
        import numpy as np

        from fluidframework_tpu.mergetree import kernel
        from fluidframework_tpu.mergetree.catchup import wire_to_host_ops
        from fluidframework_tpu.mergetree.host import (OpBuilder,
                                                       PayloadTable)
        from fluidframework_tpu.mergetree.oppack import (RunCols,
                                                         pack_run_slots,
                                                         pack_slots)
        from fluidframework_tpu.mergetree.pallas_apply import (
            apply_ops_fused_pallas)
        from fluidframework_tpu.mergetree.state import make_state
        from fluidframework_tpu.testing.traces import keystroke_trace

        docs = []
        t_max = 0
        for d in range(4):
            tail = keystroke_trace(60, seed=300 + d)
            builder = OpBuilder(PayloadTable())
            ops = []
            for op, s, r, c, m in tail:
                ops.extend(wire_to_host_ops(builder, op, s, r, c, m))
            slots = pack_run_slots(ops, base_seq=0)
            docs.append(slots)
            t_max = max(t_max, len(slots))
        packed_all, runs_all = [], []
        for slots in docs:
            p, rn = pack_slots(slots, steps=t_max)
            packed_all.append(p)
            runs_all.append(rn)
        import jax.numpy as jnp
        packed = type(packed_all[0])(*[
            jnp.stack([getattr(p, f) for p in packed_all])
            for f in packed_all[0]._fields])
        runs = RunCols(*[jnp.stack([getattr(r, f) for r in runs_all])
                         for f in RunCols._fields])
        state_a = make_state(512, 4, batch=len(docs))
        state_b = make_state(512, 4, batch=len(docs))
        out_scan = kernel._scan_ops(state_a, packed, batched=True,
                                    runs=runs)
        out_fused = apply_ops_fused_pallas(state_b, packed,
                                           interpret=True, runs=runs)
        for f in ("length", "ins_seq", "ins_client", "rem_seq",
                  "origin_op", "origin_off", "count", "anno"):
            np.testing.assert_array_equal(
                np.asarray(getattr(out_scan, f)),
                np.asarray(getattr(out_fused, f)), err_msg=f)
