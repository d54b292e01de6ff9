"""Fused apply × sequence-axis sharding (mergetree/fused_sp.py): both
drivers — the GSPMD shape-hinted body and the explicit shard_map
collective body — must be bit-identical to the scan×vmap kernel's sp
path AND to the single-shard fused reference (each already
conformance-locked to the scalar oracle). This is the off-chip proof
that the flagship fused formulation composes with sp sharding
(reference capability: O(log n) partial-length reduction,
packages/dds/merge-tree/src/partialLengths.ts:63)."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.testing.traces import gen_traces
from fluidframework_tpu.mergetree import fused_sp, kernel, pallas_apply
from fluidframework_tpu.mergetree.host import OpBuilder
from fluidframework_tpu.mergetree.oppack import PackedOps, pack_ops
from fluidframework_tpu.mergetree.state import make_state
from fluidframework_tpu.parallel.mesh import make_mesh, shard_docs

from test_kernel import build_kernel_ops, random_schedule
from test_pallas_apply import assert_states_equal


def _batched_from_traces(b, t, cap, seed):
    cols = gen_traces(b, t, seed=seed)
    ops = PackedOps(**{f: jnp.asarray(cols[f]) for f in PackedOps._fields})
    return make_state(cap, 2, batch=b), ops


def _rich_batch(seed, cap=256, batch=2):
    rng = random.Random(seed + 900)
    tuples = random_schedule(rng, n_clients=4, n_ops=40)
    host_ops = build_kernel_ops(OpBuilder(), tuples)
    packed = pack_ops([host_ops, host_ops[: len(host_ops) // 2]][:batch])
    return make_state(cap, 8, batch=batch), packed


class TestGspmdFusedSp:
    @pytest.mark.parametrize("seed,b,t,cap,sp", [(0, 8, 24, 64, 2),
                                                 (1, 8, 24, 128, 4),
                                                 (2, 16, 16, 64, 8)])
    def test_traces_match_scan_sp_and_fused_ref(self, seed, b, t, cap, sp):
        st, ops = _batched_from_traces(b, t, cap, seed)
        scan_sp = jax.jit(
            lambda s, o: kernel._scan_ops(s, o, batched=True,
                                          sp_shards=sp))(st, ops)
        ref = pallas_apply.apply_ops_fused_ref(st, ops)
        out = fused_sp.apply_ops_fused_sp(st, ops, sp)
        assert_states_equal(scan_sp, out)
        assert_states_equal(ref, out)

    @pytest.mark.parametrize("seed", range(3))
    def test_rich_schedules_match(self, seed):
        st, packed = _rich_batch(seed)
        ref = kernel.apply_ops_batched_keep(st, packed)
        out = fused_sp.apply_ops_fused_sp(st, packed, 4)
        assert_states_equal(ref, out)

    def test_overflow_flag_matches(self):
        st, ops = _batched_from_traces(4, 40, 16, 3)  # tiny capacity
        ref = kernel.apply_ops_batched_keep(st, ops)
        out = fused_sp.apply_ops_fused_sp(st, ops, 2)
        np.testing.assert_array_equal(np.asarray(ref.overflow),
                                      np.asarray(out.overflow))
        assert bool(np.asarray(ref.overflow).any())


class TestShardmapFusedSp:
    @pytest.mark.parametrize("dp,sp", [(4, 2), (2, 4)])
    def test_traces_match_on_mesh(self, dp, sp):
        mesh = make_mesh(dp=dp, sp=sp)
        st, ops = _batched_from_traces(8, 24, 64, 11)
        ref = kernel.apply_ops_batched_keep(st, ops)
        out = fused_sp.apply_ops_fused_shardmap(st, ops, mesh)
        assert_states_equal(ref, out)

    def test_rich_schedules_match_on_mesh(self):
        mesh = make_mesh(dp=2, sp=4)
        st, packed = _rich_batch(1)
        ref = kernel.apply_ops_batched_keep(st, packed)
        out = fused_sp.apply_ops_fused_shardmap(st, packed, mesh)
        assert_states_equal(ref, out)

    def test_sharded_inputs_execute(self):
        """With lane planes actually placed over the sp axis the explicit
        driver still runs and matches (the in_specs are the real
        sharding, not a resharding no-op)."""
        mesh = make_mesh(dp=4, sp=2)
        st, ops = _batched_from_traces(8, 16, 64, 5)
        st_sharded = shard_docs(mesh, st, seq_sharded=True)
        ops_sharded = shard_docs(mesh, ops)
        ref = kernel.apply_ops_batched_keep(st, ops)
        out = fused_sp.apply_ops_fused_shardmap(st_sharded, ops_sharded,
                                                mesh)
        assert_states_equal(ref, out)

    def test_capacity_divisibility_guard(self):
        mesh = make_mesh(dp=4, sp=2)
        st, ops = _batched_from_traces(4, 8, 65, 0)
        with pytest.raises(ValueError, match="not divisible"):
            fused_sp.apply_ops_fused_shardmap(st, ops, mesh)


class TestFusedSpInsertRun:
    def _run_batch(self):
        from fluidframework_tpu.mergetree.catchup import wire_to_host_ops
        from fluidframework_tpu.mergetree.host import (OpBuilder,
                                                       PayloadTable)
        from fluidframework_tpu.mergetree.oppack import (RunCols,
                                                         pack_run_slots,
                                                         pack_slots)
        from fluidframework_tpu.testing.traces import keystroke_trace

        docs, t_max = [], 0
        for d in range(4):
            tail = keystroke_trace(60, seed=700 + d)
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
        packed = type(packed_all[0])(*[
            jnp.stack([getattr(p, f) for p in packed_all])
            for f in packed_all[0]._fields])
        runs = RunCols(*[jnp.stack([getattr(r, f) for r in runs_all])
                         for f in RunCols._fields])
        return packed, runs

    def test_gspmd_runs_variant_matches_scan(self):
        packed, runs = self._run_batch()
        ref = kernel._scan_ops(make_state(512, 4, batch=4), packed,
                               batched=True, runs=runs)
        out = fused_sp.apply_ops_fused_sp(make_state(512, 4, batch=4),
                                          packed, 4, runs=runs)
        assert_states_equal(ref, out)

    def test_shardmap_runs_variant_matches_scan(self):
        mesh = make_mesh(dp=4, sp=2)
        packed, runs = self._run_batch()
        ref = kernel._scan_ops(make_state(512, 4, batch=4), packed,
                               batched=True, runs=runs)
        out = fused_sp.apply_ops_fused_shardmap(
            make_state(512, 4, batch=4), packed, mesh, runs=runs)
        assert_states_equal(ref, out)


class TestPipelineFusedSp:
    def test_full_step_fused_sp_matches_scan_sp(self):
        """make_full_step(sp_shards>1, fused_apply=True) no longer raises
        (the round-2..4 deferral) and is bit-identical to the scan path."""
        from fluidframework_tpu.server.pipeline import make_full_step
        from fluidframework_tpu.server import ticket_kernel as tk

        def example(batch, cap, steps, seed):
            cols = gen_traces(batch, steps, seed=seed)
            ops = PackedOps(**{f: jnp.asarray(cols[f])
                               for f in PackedOps._fields})
            raw = tk.RawOps(client=ops.client, client_seq=ops.seq,
                            ref_seq=ops.ref_seq)
            return (tk.make_ticket_state(4, batch=batch),
                    make_state(cap, 1, batch=batch), raw, ops)

        args = example(8, 64, 12, 21)
        _, m_scan, tick_scan, len_scan = jax.jit(
            make_full_step(sp_shards=2))(*args)
        _, m_fsp, tick_fsp, len_fsp = jax.jit(
            make_full_step(sp_shards=2, fused_apply=True))(*args)
        assert_states_equal(m_scan, m_fsp)
        np.testing.assert_array_equal(np.asarray(tick_scan.seq),
                                      np.asarray(tick_fsp.seq))
        np.testing.assert_array_equal(np.asarray(len_scan),
                                      np.asarray(len_fsp))

    @pytest.mark.parametrize("pass_mesh", [False, True])
    def test_full_step_fused_sp_on_sharded_mesh_inputs(self, pass_mesh):
        """The composed step executes under real dp×sp placements — the
        dryrun_multichip configuration (GSPMD inserts the collectives).
        With the mesh passed, the summary runs per shard (shard_map), as
        it must on a TPU."""
        from fluidframework_tpu.server.pipeline import make_full_step
        from fluidframework_tpu.server import ticket_kernel as tk

        mesh = make_mesh(dp=4, sp=2)
        cols = gen_traces(8, 8, seed=33)
        ops = PackedOps(**{f: jnp.asarray(cols[f])
                           for f in PackedOps._fields})
        raw = tk.RawOps(client=ops.client, client_seq=ops.seq,
                        ref_seq=ops.ref_seq)
        tstate = tk.make_ticket_state(4, batch=8)
        mstate = make_state(64, 1, batch=8)
        ref = jax.jit(make_full_step(sp_shards=2))(
            tstate, mstate, raw, ops)

        tstate_s = shard_docs(mesh, tstate)
        mstate_s = shard_docs(mesh, mstate, seq_sharded=True)
        raw_s = shard_docs(mesh, raw)
        ops_s = shard_docs(mesh, ops)
        out = jax.jit(make_full_step(
            sp_shards=2, fused_apply=True, mesh=mesh if pass_mesh else None))(
            tstate_s, mstate_s, raw_s, ops_s)
        assert_states_equal(ref[1], out[1])
        np.testing.assert_array_equal(np.asarray(ref[3]),
                                      np.asarray(out[3]))


class TestFusedSpLongDocument:
    def test_large_capacity_sharded_lane_axis(self):
        """Long-document shape: a 4096-lane capacity axis over sp=8 (the
        per-shard tile is 512 lanes — VMEM-class on TPU). Bit-identity
        against the single-shard fused reference at a scale where the
        two-level scan structure actually matters."""
        mesh = make_mesh(dp=1, sp=8)
        st, ops = _batched_from_traces(2, 48, 4096, 19)
        ref = pallas_apply.apply_ops_fused_ref(st, ops)
        g = fused_sp.apply_ops_fused_sp(st, ops, 8)
        sm = fused_sp.apply_ops_fused_shardmap(st, ops, mesh,
                                               dp_axis="dp")
        assert_states_equal(ref, g)
        assert_states_equal(ref, sm)
