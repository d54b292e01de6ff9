"""Pallas summary-length kernel vs the jnp reference (interpret mode on
the CPU backend; the real Mosaic path engages on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluidframework_tpu.mergetree import kernel
from fluidframework_tpu.mergetree.oppack import PackedOps
from fluidframework_tpu.mergetree.pallas_ops import (_jnp_summary_lengths,
                                                     _summary_cols,
                                                     summary_lengths)
from fluidframework_tpu.mergetree.state import make_state
from fluidframework_tpu.parallel.mesh import make_mesh, shard_docs


def batched_state_after_ops(batch=5, capacity=64, steps=30, seed=0):
    from fluidframework_tpu.testing.traces import gen_traces
    cols = gen_traces(batch, steps, seed=seed)
    ops = PackedOps(**{f: jnp.asarray(cols[f]) for f in PackedOps._fields})
    state = make_state(capacity, 1, batch=batch)
    return kernel.apply_ops_batched(state, ops)


class TestSummaryLengths:
    def test_interpret_matches_jnp(self):
        state = batched_state_after_ops()
        ref = np.asarray(_jnp_summary_lengths(*_summary_cols(state)))
        out = np.asarray(summary_lengths(state, interpret=True))
        np.testing.assert_array_equal(out, ref)

    def test_nonaligned_batch_padding(self):
        # batch=5 is not a multiple of the 8-doc tile: padding path.
        state = batched_state_after_ops(batch=5)
        out = np.asarray(summary_lengths(state, interpret=True))
        assert out.shape == (5,)

    def test_matches_full_visibility_reduction(self):
        """The simplified acked-perspective predicate must equal the full
        kernel.visibility reduction used previously."""
        state = batched_state_after_ops(batch=7, steps=40, seed=3)
        full = np.asarray(jax.vmap(
            lambda s: kernel.visibility(s, s.seq, -2)[1].sum())(state))
        out = np.asarray(summary_lengths(state, interpret=True))
        np.testing.assert_array_equal(out, full)

    def test_dispatch_cpu_uses_jnp(self):
        state = batched_state_after_ops(batch=3)
        out = np.asarray(summary_lengths(state))  # cpu backend -> jnp path
        ref = np.asarray(_jnp_summary_lengths(*_summary_cols(state)))
        np.testing.assert_array_equal(out, ref)

    @pytest.mark.parametrize("dp,sp,interpret", [(2, 2, False), (2, 2, True),
                                                 (1, 4, True), (4, 1, True)])
    def test_sharded_matches_one_device(self, dp, sp, interpret):
        """On a mesh each shard reduces its own capacity slice (the live
        count shifted to shard-local slots) and the sums meet over 'sp':
        the same totals as one device, Pallas body or jnp."""
        state = batched_state_after_ops(batch=8, capacity=64, steps=40,
                                        seed=5)
        ref = np.asarray(_jnp_summary_lengths(*_summary_cols(state)))
        mesh = make_mesh(dp=dp, sp=sp, devices=jax.devices()[:dp * sp])
        placed = shard_docs(mesh, state, seq_sharded=True)
        out = jax.jit(lambda s: summary_lengths(s, interpret=interpret,
                                                mesh=mesh))(placed)
        np.testing.assert_array_equal(np.asarray(out), ref)
