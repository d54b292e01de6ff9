"""Pallas TPU kernels for merge-tree reductions.

The summary-length pass — per-document total visible length at the acked
perspective — runs once per pipeline step over the whole `[docs, capacity]`
segment table (SURVEY.md §3 hot loop (d): summary gather). The XLA version
materializes the visibility mask and masked lengths as separate `[B, C]`
intermediates; this Pallas kernel fuses predicate + mask + reduce into one
VMEM pass per document tile, so each segment column is read from HBM
exactly once and nothing is written back but the `[B]` totals.

At the acked/global perspective (client = OBSERVER, ref_seq = state.seq)
the predicate needs only (ins_seq, rem_seq, count): pending-insert and
overlap-remove columns cannot affect visibility at an acked ref_seq.

`summary_lengths()` dispatches: Pallas on the TPU backend, the jnp
formulation on the CPU. `interpret=True` runs the same kernel through the
Pallas interpreter for CPU correctness tests. A kernel the TPU compiler
refuses fails loudly; there is no probe and no fallback.

GSPMD cannot partition a Mosaic kernel, so on a mesh the caller passes it:
the reduction then runs per shard under shard_map (docs over 'dp', the
capacity axis over 'sp'), and the per-shard sums are added over 'sp'.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .state import DocState

_DOC_TILE = 8  # int32 sublane tile


def _summary_len_kernel(length_ref, ins_seq_ref, rem_seq_ref, count_ref,
                        seq_ref, out_ref):
    idx = jax.lax.broadcasted_iota(jnp.int32, length_ref.shape, 1)
    count = count_ref[:, 0][:, None]
    seq = seq_ref[:, 0][:, None]
    vis = ((idx < count) & (ins_seq_ref[:] <= seq)
           & ~(rem_seq_ref[:] <= seq))
    out_ref[:, 0] = jnp.sum(jnp.where(vis, length_ref[:], 0), axis=1)


def _pallas_summary_lengths(length, ins_seq, rem_seq, count, seq,
                            interpret: bool) -> jnp.ndarray:
    from jax.experimental import pallas as pl

    batch, capacity = length.shape
    padded = ((batch + _DOC_TILE - 1) // _DOC_TILE) * _DOC_TILE
    pad = padded - batch

    def pad_rows(x, fill):
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=fill) if pad else x

    grid = (padded // _DOC_TILE,)
    row_block = lambda block: pl.BlockSpec(  # noqa: E731
        block, lambda i: (i, 0))
    out = pl.pallas_call(
        _summary_len_kernel,
        out_shape=jax.ShapeDtypeStruct((padded, 1), length.dtype),
        grid=grid,
        in_specs=[row_block((_DOC_TILE, capacity)),
                  row_block((_DOC_TILE, capacity)),
                  row_block((_DOC_TILE, capacity)),
                  row_block((_DOC_TILE, 1)),
                  row_block((_DOC_TILE, 1))],
        out_specs=row_block((_DOC_TILE, 1)),
        interpret=interpret,
    )(pad_rows(length, 0), pad_rows(ins_seq, 1), pad_rows(rem_seq, 0),
      pad_rows(count.reshape(batch, 1), 0), pad_rows(seq.reshape(batch, 1), 0))
    return out[:batch, 0]


def _jnp_summary_lengths(length, ins_seq, rem_seq, count, seq
                         ) -> jnp.ndarray:
    idx = jax.lax.broadcasted_iota(jnp.int32, length.shape, 1)
    seq = seq[:, None]
    vis = ((idx < count[:, None]) & (ins_seq <= seq) & ~(rem_seq <= seq))
    return jnp.sum(jnp.where(vis, length, 0), axis=1)


def _local_summary_lengths(length, ins_seq, rem_seq, count, seq,
                           interpret: bool) -> jnp.ndarray:
    if interpret or jax.default_backend() == "tpu":
        return _pallas_summary_lengths(length, ins_seq, rem_seq, count, seq,
                                       interpret=interpret)
    return _jnp_summary_lengths(length, ins_seq, rem_seq, count, seq)


def _sharded_summary_lengths(cols, mesh: Mesh, interpret: bool
                             ) -> jnp.ndarray:
    local = cols[0].shape[1] // mesh.shape["sp"]

    def shard(length, ins_seq, rem_seq, count, seq):
        # The shard's columns start at this global slot: shift the
        # per-doc live count into shard-local slots.
        first = jax.lax.axis_index("sp") * local
        part = _local_summary_lengths(length, ins_seq, rem_seq,
                                      count - first, seq, interpret)
        return jax.lax.psum(part, "sp")

    planes, docs = P("dp", "sp"), P("dp")
    # check_vma off: pallas_call's out_shape carries no varying-axes type.
    return shard_map(shard, mesh=mesh,
                     in_specs=(planes, planes, planes, docs, docs),
                     out_specs=docs, check_vma=False)(*cols)


def _summary_cols(state: DocState) -> tuple:
    return (state.length, state.ins_seq, state.rem_seq, state.count,
            state.seq)


def summary_lengths(state: DocState, interpret: bool = False,
                    mesh: Mesh | None = None) -> jnp.ndarray:
    """Per-document visible length at the acked perspective for a BATCHED
    DocState. Pallas on the TPU, jnp on the CPU; per shard on `mesh`."""
    cols = _summary_cols(state)
    if mesh is not None:
        return _sharded_summary_lengths(cols, mesh, interpret)
    return _local_summary_lengths(*cols, interpret=interpret)
