"""The fused server pipeline step: deli ticketing + merge-tree apply +
summary-length reduction in one jit program — the device half of a
partition lambda (reference Deli -> Scriptorium/Scribe stage fusion,
SURVEY.md §2.6.3 pipeline parallelism).

The ticketing output FEEDS the apply: each op's assigned sequence number and
msn replace the packed columns, and ops the sequencer rejected (nack) or
dropped (duplicate) are turned into NOOPs before the merge-tree sees them —
the document state can only contain what the sequencer admitted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..mergetree import kernel
from ..mergetree.oppack import OpKind
from ..mergetree.pallas_ops import summary_lengths
from . import ticket_kernel as tk


def make_full_step(sp_shards: int = 1, fused_apply: bool = False,
                   mesh=None):
    """Build the fused pipeline step for a given sequence-parallel factor:
    with sp_shards > 1 the merge kernel's visibility prefix sums use the
    two-level collective-scan formulation (kernel._cumsum_sp), so a
    capacity axis sharded over 'sp' resolves positions with shard-local
    cumsums + a tiny cross-shard offset exchange instead of a serialized
    full-axis scan (SURVEY.md §5 long-context mapping).

    fused_apply=True routes the merge apply through the VMEM-resident
    Pallas kernel (mergetree/pallas_apply.py — one HBM read+write for the
    whole op stream). fused_apply with sp_shards > 1 composes the SAME
    fused formulation with sequence-axis sharding (mergetree/fused_sp.py):
    per-shard lane tiles with two-level collective prefix sums, so long
    documents and the flagship kernel are no longer mutually exclusive.

    mesh: the (dp, sp) mesh the inputs are placed on. The summary
    reduction's Pallas kernel runs per shard on it (GSPMD cannot partition
    a Mosaic kernel), so on a TPU a sharded step needs it.

    Naming note: this flag is the capacity-gated KERNEL experiment bench
    drives (stamped `fused_apply_kernel_exp` in BENCH records since
    round 8). The PRODUCTION serving path's `fused_apply` stamp means
    something stronger — the sequencer actually ran scanned multi-window
    serving megakernels (serve_step.serve_megakernel,
    docs/serving_pipeline.md R10)."""

    def full_step(tstate, mstate, raw, ops):
        """(ticket_state, merge_state, RawOps, PackedOps) ->
        (ticket_state, merge_state, Ticketed, per-doc visible length)."""
        tstate, ticketed = tk._scan_tickets(tstate, raw, batched=True)
        admitted = ticketed.seq > 0
        ops2 = ops._replace(
            kind=jnp.where(admitted, ops.kind, OpKind.NOOP),
            seq=jnp.where(admitted, ticketed.seq, ops.seq),
            msn=jnp.where(admitted, ticketed.min_seq, ops.msn),
        )
        from ..mergetree.pallas_apply import FUSED_MAX_CAPACITY
        if fused_apply and sp_shards > 1:
            from ..mergetree.fused_sp import _fused_sp_body
            mstate = _fused_sp_body(mstate, ops2, sp_shards)
        elif fused_apply and mstate.capacity <= FUSED_MAX_CAPACITY:
            from ..mergetree.pallas_apply import apply_ops_fused_pallas
            mstate = apply_ops_fused_pallas(mstate, ops2)
        else:
            # Very large capacities exceed the fused kernel's VMEM budget;
            # the scan×vmap kernel covers them.
            mstate = kernel._scan_ops(mstate, ops2, batched=True,
                                      sp_shards=sp_shards)
        # Summary-length reduction (semantics == visibility(s, s.seq, ...)):
        # the fused Pallas pass on the TPU (mergetree/pallas_ops.py).
        total_len = summary_lengths(mstate, mesh=mesh)
        return tstate, mstate, ticketed, total_len

    return full_step


full_step = make_full_step(1)
