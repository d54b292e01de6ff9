"""Fused merge-tree apply × sequence-axis sharding.

The flagship fused formulation (pallas_apply.py) and long-document
sequence parallelism were mutually exclusive through round 4
(`pipeline.make_full_step` raised). This module composes them: the SAME
batched body (`pallas_apply._apply_one_batched`) runs on per-shard lane
tiles, with the cross-shard coordination a handful of scalar exchanges
per op phase — exactly the partial-length reduction the reference keeps
in its O(log n) PartialSequenceLengths trees
(reference packages/dds/merge-tree/src/partialLengths.ts:63), done here
as mesh collectives over the sharded capacity axis.

Two interchangeable drivers, bit-identical to each other, to the
single-shard fused reference, and to the scan×vmap kernel's sp path
(tests/test_fused_sp.py):

- `apply_ops_fused_sp` (GSPMD): the lane context's prefix sum uses the
  two-level reshape formulation (`kernel._cumsum_sp`'s shape hint), so
  under jit with the capacity axis sharded over 'sp' XLA keeps the inner
  cumsum shard-local and lowers the totals exchange to a tiny
  all-gather over ICI. Drop-in for the pipeline step — no mesh handle
  needed.
- `apply_ops_fused_shardmap` (explicit): shard_map over the mesh with a
  collective lane context — psum/pmin for the any/first/masked-sum
  reductions, a two-level all-gather scan for visibility prefix sums,
  and a single batched ppermute carrying the boundary rows of ALL ~17
  segment planes per structural shift. This is the explicit exchange
  schedule of the composed kernel: per-shard lane tiles stay resident
  (VMEM-class working sets on TPU) and every cross-shard message is
  O(B) scalars or O(B·shift) boundary rows, never the table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from . import pallas_apply as pa
from .oppack import PackedOps
from .state import DocState


def _drive(st, k, a, t_steps, fields, cols, ln, with_runs):
    def get_op(t):
        return {f: jax.lax.dynamic_slice_in_dim(cols[f], t, 1, axis=1)
                for f in fields}

    return pa._stream_loop(st, t_steps, get_op, k, a, ln,
                           with_runs=with_runs)


# ---------------------------------------------------------------------------
# GSPMD lane context: shape-hinted two-level scan, everything else local
# ---------------------------------------------------------------------------

def _two_level_cumsum_excl(sp_shards: int):
    def cumsum_excl(x):
        b, c = x.shape
        if sp_shards <= 1 or c % sp_shards:
            return jnp.cumsum(x, axis=-1) - x
        blocks = x.reshape(b, sp_shards, c // sp_shards)
        local = jnp.cumsum(blocks, axis=-1)
        totals = local[..., -1]
        offsets = jnp.cumsum(totals, axis=-1) - totals  # exclusive
        return (local + offsets[..., None]).reshape(b, c) - x

    return cumsum_excl


def gspmd_lanes(total: int, sp_shards: int) -> pa.Lanes:
    """Full-axis lane ops with the prefix sum reshaped so GSPMD keeps it
    shard-local under an sp-sharded capacity axis (kernel._cumsum_sp)."""
    ln = pa.local_lanes(total, lambda x, n: jnp.roll(x, n, axis=1))
    return ln._replace(cumsum_excl=_two_level_cumsum_excl(sp_shards))


def _fused_sp_body(state: DocState, ops: PackedOps, sp_shards: int,
                   runs=None) -> DocState:
    """Un-jitted GSPMD body — composable inside a larger jitted step
    (pipeline.make_full_step calls this directly)."""
    st, k, a = pa._to_planes(state)
    fields, cols = pa.op_cols(ops, runs)
    ln = gspmd_lanes(state.length.shape[-1], sp_shards)
    out = _drive(st, k, a, ops.kind.shape[-1], fields, cols, ln,
                 runs is not None)
    return pa._from_planes(out, k, a)


@functools.partial(jax.jit, static_argnums=(2,))
# fluidlint: disable=MISSING_DONATE — non-donating by contract (docstring):
# overflow recovery re-applies from the retained sharded input.
def apply_ops_fused_sp(state: DocState, ops: PackedOps, sp_shards: int,
                       runs=None) -> DocState:
    """The fused formulation with sp-aware prefix sums: jit this with the
    capacity axis sharded over 'sp' (parallel.mesh.shard_docs
    seq_sharded=True) and GSPMD inserts the collectives. Non-donating."""
    return _fused_sp_body(state, ops, sp_shards, runs)


# ---------------------------------------------------------------------------
# shard_map lane context: explicit collectives, per-shard lane tiles
# ---------------------------------------------------------------------------

def shard_lanes(total: int, local_width: int, sp: int,
                axis: str) -> pa.Lanes:
    """Lane primitives over a [B, total/sp] shard tile. Per-doc scalars
    (slot indices, any/masked reductions) come out of psum/pmin so every
    shard holds identical copies — the scalar planes (count/seq/...)
    evolve replicated, and out_specs can leave them unsharded."""
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def iota(shape):
        return idx * local_width + pa._local_iota(shape)

    def roll_many(xs, n):
        # One exchange for the whole plane set: stack the n boundary
        # columns of every plane into a single [P, B, n] ppermute. The
        # wrap from the last shard into shard 0 mirrors jnp.roll's
        # cyclic wrap — those lanes are always overwritten by the
        # caller's masked fills, same as the single-shard kernel.
        if n >= local_width:
            raise ValueError(
                f"shift {n} >= shard tile {local_width}: raise capacity "
                f"or lower sp")
        tails = jnp.stack([x[:, local_width - n:] for x in xs])
        incoming = jax.lax.ppermute(tails, axis, perm)
        return [jnp.concatenate([incoming[i], x[:, :-n]], axis=1)
                for i, x in enumerate(xs)]

    def cumsum_excl(x):
        # Two-level collective scan (parallel/seq_scan.py): local cumsum
        # + all-gathered shard totals, masked to my predecessors.
        incl = jnp.cumsum(x, axis=1)
        totals = jax.lax.all_gather(incl[:, -1:], axis, axis=-1,
                                    tiled=True)  # [B, sp]
        mask = jnp.arange(sp) < idx
        offset = jnp.sum(jnp.where(mask, totals, 0), axis=-1,
                         keepdims=True)
        return incl + offset - x

    return pa.Lanes(
        total=total,
        iota=iota,
        any_lane=lambda m: jax.lax.psum(
            jnp.sum(m.astype(jnp.int32), axis=1, keepdims=True), axis) > 0,
        first_true=lambda m: jax.lax.pmin(
            jnp.min(jnp.where(m, iota(m.shape), total), axis=1,
                    keepdims=True), axis),
        masked_scalar=lambda v, m: jax.lax.psum(
            jnp.sum(jnp.where(m, v, 0), axis=1, keepdims=True), axis),
        cumsum_excl=cumsum_excl,
        roll=lambda x, n: roll_many([x], n)[0],
        roll_many=roll_many,
    )


def apply_ops_fused_shardmap(state: DocState, ops: PackedOps, mesh: Mesh,
                             runs=None, dp_axis: str = "dp",
                             sp_axis: str = "sp") -> DocState:
    """Explicit-collective fused-sp apply: per-shard lane tiles under
    shard_map, cross-shard exchange between phases. Non-donating."""
    sp = mesh.shape[sp_axis]
    b, c = state.length.shape
    if c % sp:
        raise ValueError(f"capacity {c} not divisible by sp={sp}")
    dp = dp_axis if dp_axis in mesh.shape else None

    st, k, a = pa._to_planes(state)
    fields, cols = pa.op_cols(ops, runs)
    t_steps = ops.kind.shape[-1]
    with_runs = runs is not None

    def spec(name):
        lane_plane = st[name].shape[-1] == c
        return P(dp, sp_axis) if lane_plane else P(dp, None)

    in_specs = ({n: spec(n) for n in st},
                {f: P(dp, None) for f in fields})
    out_specs = {n: spec(n) for n in st}

    def body(st_l, cols_l):
        ln = shard_lanes(c, c // sp, sp, sp_axis)
        return _drive(st_l, k, a, t_steps, fields, cols_l, ln, with_runs)

    out = shard_map(body, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs)(st, cols)
    return pa._from_planes(out, k, a)
