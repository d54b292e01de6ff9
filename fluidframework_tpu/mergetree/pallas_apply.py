"""Fused Pallas apply: the WHOLE op stream in one VMEM-resident kernel.

The scan×vmap kernel (kernel.py) re-reads and re-writes the full segment
table from HBM ~10× per op (three roll-select shifts + phase writes over
~15 columns) — measured bandwidth-bound (PERF.md). This kernel instead
tiles documents into VMEM blocks, applies ALL T ops to the resident block
with a `fori_loop`, and writes the state back once:

    HBM traffic: 2 state passes TOTAL (+ tiny op columns), vs ~10·T passes.

Semantics are kernel.py's apply_one exactly, re-expressed with a leading
doc axis and with the primitives Mosaic lowers well:
- prefix sums  -> Hillis-Steele doubling over lane rolls (log2(C) steps);
- argmax       -> masked min-over-iota reduction;
- 3-D columns (rem_clients [C,K], anno [C,A]) -> K/A separate 2-D planes.

The same batched body runs in three modes: plain jnp (the reference the
tests hold the kernel to), Pallas interpret (CPU conformance tests),
Pallas TPU (the fast path). Callers pick the mode; nothing here probes the
backend or falls back — a kernel the TPU compiler refuses fails loudly.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple

import jax
import jax.numpy as jnp

from .constants import DEV_NO_REMOVE, DEV_UNASSIGNED
from .oppack import OpKind, PackedOps
from .state import DocState

DOC_TILE = 128  # max docs per VMEM block (int32 sublane multiple)
# Per-plane VMEM element budget for the resident block: 128 docs × 512
# slots measured to fit (~4.4MB across ~17 planes, ×2 for the aliased
# in/out windows + loop temporaries under the ~16MB budget). Larger
# capacities shrink the doc tile instead of falling off the fused path.
_TILE_ELEMS = 128 * 512
# Tile floor is 8 docs (int32 sublane multiple), so the fused kernel
# covers capacities up to _TILE_ELEMS/8; callers route anything larger
# to the scan×vmap kernel (pipeline.make_full_step does).
FUSED_MAX_CAPACITY = _TILE_ELEMS // 8


def tile_for_capacity(capacity: int) -> int:
    """Docs per VMEM block at this capacity: full 128-doc tiles up to
    C=512, then proportional (_TILE_ELEMS // C, floored to a multiple of
    8, min 8) so the resident block stays inside VMEM."""
    tile = min(DOC_TILE, _TILE_ELEMS // max(capacity, 1))
    return max(8, (tile // 8) * 8)


# ---------------------------------------------------------------------------
# the batched body (pure jnp on [B, C] planes; lane primitives injected
# per mode via a Lanes context)
# ---------------------------------------------------------------------------

class Lanes(NamedTuple):
    """The lane-axis primitives the batched body is written against.

    Everything the fused formulation does along the segment axis funnels
    through these seven operations, so swapping the context retargets the
    SAME body: local jnp (reference), Pallas/Mosaic (TPU VMEM kernel), a
    GSPMD two-level scan (sp-sharded capacity under jit), or shard_map
    collectives (fused_sp.py — per-shard lane tiles with explicit
    cross-shard exchange). `total` is the GLOBAL lane count: `iota`
    returns global lane indices and `first_true` uses `total` as its
    no-match sentinel, so per-doc scalars (count/slot/...) stay global
    under every context."""

    total: int
    iota: Callable        # (local_shape) -> global lane indices
    any_lane: Callable    # (mask[B, Cl]) -> bool [B, 1] (global any)
    first_true: Callable  # (mask[B, Cl]) -> first global lane, else total
    masked_scalar: Callable  # (values, mask) -> global masked sum [B, 1]
    cumsum_excl: Callable    # exclusive prefix sum along global lanes
    roll: Callable           # cyclic shift along the global lane axis
    roll_many: Callable      # ([arrays], n) -> batched roll (one exchange)


def _local_iota(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def local_lanes(total: int, roll) -> Lanes:
    """Single-shard context: the full lane axis is resident (jnp driver
    and the Pallas kernel, which injects pltpu.roll)."""

    def cumsum_excl(x):
        # Hillis-Steele doubling: log2(C) shift+adds, Mosaic-friendly
        # (jnp.cumsum does not lower on the lane axis in the kernel).
        lane = _local_iota(x.shape)
        t = x
        k = 1
        while k < total:
            t = t + jnp.where(lane >= k, roll(t, k), 0)
            k *= 2
        return t - x

    return Lanes(
        total=total,
        iota=_local_iota,
        any_lane=lambda m: jnp.sum(m.astype(jnp.int32), axis=1,
                                   keepdims=True) > 0,
        first_true=lambda m: jnp.min(
            jnp.where(m, _local_iota(m.shape), total), axis=1,
            keepdims=True),
        masked_scalar=lambda v, m: jnp.sum(jnp.where(m, v, 0), axis=1,
                                           keepdims=True),
        cumsum_excl=cumsum_excl,
        roll=roll,
        roll_many=lambda xs, n: [roll(x, n) for x in xs],
    )


def _visibility(st: Dict[str, jnp.ndarray], ref, client, k_slots,
                ln: Lanes):
    lane = ln.iota(st["length"].shape)
    valid = lane < st["count"]
    inserted = (st["ins_seq"] <= ref) | (st["ins_client"] == client)
    removed = st["rem_seq"] <= ref
    for i in range(k_slots):
        removed = removed | (st[f"rc{i}"] == client)
    vis = valid & inserted & ~removed
    vlen = jnp.where(vis, st["length"], 0)
    return vis, vlen, ln.cumsum_excl(vlen)


_SEG_PLANES = ("length", "ins_seq", "ins_client", "local_seq", "rem_seq",
               "rem_local_seq", "origin_op", "origin_off")


def _shift_right(st, shift_mask, k_slots, a_slots, ln: Lanes, by: int = 1):
    names = _SEG_PLANES + tuple(f"rc{i}" for i in range(k_slots)) + \
        tuple(f"an{i}" for i in range(a_slots))
    rolled = ln.roll_many([st[name] for name in names], by)
    out = dict(st)
    for name, r in zip(names, rolled):
        out[name] = jnp.where(shift_mask, r, st[name])
    return out


def _ensure_boundary(st, pos, ref, client, enabled, k_slots, a_slots,
                     ln: Lanes):
    vis, vlen, cum = _visibility(st, ref, client, k_slots, ln)
    inside = vis & (cum < pos) & (pos < cum + vlen)
    do = enabled & ln.any_lane(inside)
    slot = ln.first_true(inside)
    off = pos - ln.masked_scalar(cum, inside)
    parent_len = ln.masked_scalar(st["length"], inside)
    lane = ln.iota(st["length"].shape)
    g = _shift_right(st, (lane >= slot + 1) & do, k_slots, a_slots, ln)
    g["count"] = st["count"] + do.astype(jnp.int32)
    is_left = do & (lane == slot)
    is_right = do & (lane == slot + 1)
    g["length"] = jnp.where(is_left, off,
                            jnp.where(is_right, parent_len - off,
                                      g["length"]))
    g["origin_off"] = jnp.where(is_right, g["origin_off"] + off,
                                g["origin_off"])
    return g


def _insert_phase(st, op, enabled, view, k_slots, a_slots, ln: Lanes):
    vis, vlen, cum = view
    lane = ln.iota(st["length"].shape)
    is_local = op["seq"] == DEV_UNASSIGNED
    in_run = cum == op["pos1"]
    tomb = st["rem_seq"] <= op["ref_seq"]
    acked_ins = st["ins_seq"] != DEV_UNASSIGNED
    stop = in_run & (vis | (~tomb & (is_local | acked_ins))
                     | (lane >= st["count"]))
    found = ln.any_lane(stop)
    bad = enabled & ~found
    enabled = enabled & found
    slot = ln.first_true(stop)
    g = _shift_right(st, (lane >= slot) & enabled, k_slots, a_slots, ln)
    g["count"] = st["count"] + enabled.astype(jnp.int32)
    here = enabled & (lane == slot)
    g["length"] = jnp.where(here, op["new_len"], g["length"])
    g["ins_seq"] = jnp.where(here, op["seq"], g["ins_seq"])
    g["ins_client"] = jnp.where(here, op["client"], g["ins_client"])
    g["local_seq"] = jnp.where(
        here, jnp.where(is_local, op["local_seq"], 0), g["local_seq"])
    g["rem_seq"] = jnp.where(here, DEV_NO_REMOVE, g["rem_seq"])
    g["rem_local_seq"] = jnp.where(here, 0, g["rem_local_seq"])
    g["origin_op"] = jnp.where(here, op["op_id"], g["origin_op"])
    g["origin_off"] = jnp.where(here, 0, g["origin_off"])
    for i in range(k_slots):
        g[f"rc{i}"] = jnp.where(here, -1, g[f"rc{i}"])
    for i in range(a_slots):
        g[f"an{i}"] = jnp.where(here, -1, g[f"an{i}"])
    g["overflow"] = g["overflow"] | bad
    return g


def _insert_run_phase(st, op, enabled, view, k_slots, a_slots, ln: Lanes):
    """kernel._insert_run_phase on planes: up to RUN_K packed
    cursor-advance inserts land as contiguous rows at ONE tie-break slot
    — one shift-by-K + K masked fills; padding rows (len 0) born dead."""
    from .oppack import RUN_K

    vis, vlen, cum = view
    lane = ln.iota(st["length"].shape)
    in_run = cum == op["pos1"]
    tomb = st["rem_seq"] <= op["ref_seq"]
    acked_ins = st["ins_seq"] != DEV_UNASSIGNED
    stop = in_run & (vis | (~tomb & acked_ins) | (lane >= st["count"]))
    found = ln.any_lane(stop)
    bad = enabled & ~found
    enabled = enabled & found
    slot = ln.first_true(stop)
    g = _shift_right(st, (lane >= slot) & enabled, k_slots, a_slots, ln,
                     by=RUN_K)
    g["count"] = st["count"] + enabled.astype(jnp.int32) * RUN_K
    rel = lane - slot
    here = enabled & (rel >= 0) & (rel < RUN_K)

    def pick(prefix, pad):
        out = jnp.full_like(st["length"], pad)
        for k_i in range(RUN_K):
            out = jnp.where(rel == k_i, op[f"{prefix}{k_i}"], out)
        return out

    row_len = pick("rl", 0)
    row_seq = pick("rs", 0)
    row_id = pick("ri", -1)
    live = here & (row_len > 0)
    dead = here & (row_len == 0)
    g["length"] = jnp.where(here, row_len, g["length"])
    g["ins_seq"] = jnp.where(live, row_seq,
                             jnp.where(dead, 0, g["ins_seq"]))
    g["ins_client"] = jnp.where(live, op["client"],
                                jnp.where(dead, -1, g["ins_client"]))
    g["local_seq"] = jnp.where(here, 0, g["local_seq"])
    g["rem_seq"] = jnp.where(live, DEV_NO_REMOVE,
                             jnp.where(dead, 0, g["rem_seq"]))
    g["rem_local_seq"] = jnp.where(here, 0, g["rem_local_seq"])
    g["origin_op"] = jnp.where(here, row_id, g["origin_op"])
    g["origin_off"] = jnp.where(here, 0, g["origin_off"])
    for i in range(k_slots):
        g[f"rc{i}"] = jnp.where(here, -1, g[f"rc{i}"])
    for i in range(a_slots):
        g[f"an{i}"] = jnp.where(here, -1, g[f"an{i}"])
    g["overflow"] = g["overflow"] | bad
    return g


def _range_targets(st, op, view):
    vis, vlen, cum = view
    return vis & (vlen > 0) & (cum >= op["pos1"]) & \
        (cum + vlen <= op["pos2"])


def _append_overlap(st, need, client, k_slots):
    """Place client into the first free overlap slot (>=1) where need."""
    taken_before = jnp.zeros_like(need)
    placed = dict(st)
    for i in range(1, k_slots):
        free_i = st[f"rc{i}"] == -1
        first_free = free_i & ~taken_before
        placed[f"rc{i}"] = jnp.where(need & first_free, client,
                                     st[f"rc{i}"])
        taken_before = taken_before | free_i
    # kernel._append_overlap only writes when some slot is free; with no
    # free slot nothing changes (the overflow check below catches it).
    return placed


def _remove_phase(st, op, enabled, view, k_slots, ln: Lanes):
    target = _range_targets(st, op, view) & enabled
    is_local = op["seq"] == DEV_UNASSIGNED
    fresh = target & (st["rem_seq"] == DEV_NO_REMOVE)
    pend_overwrite = target & (st["rem_seq"] == DEV_UNASSIGNED) & ~is_local
    already = target & (st["rem_seq"] != DEV_NO_REMOVE) & ~pend_overwrite

    g = dict(st)
    g["rem_seq"] = jnp.where(
        fresh, jnp.where(is_local, DEV_UNASSIGNED, op["seq"]),
        jnp.where(pend_overwrite, op["seq"], st["rem_seq"]))
    g["rem_local_seq"] = jnp.where(
        fresh & is_local, op["local_seq"],
        jnp.where(pend_overwrite, 0, st["rem_local_seq"]))
    prior = st["rc0"]
    g["rc0"] = jnp.where(fresh | pend_overwrite, op["client"], st["rc0"])
    displaced = pend_overwrite & (prior != op["client"])
    g2 = _append_overlap(g, displaced, prior, k_slots)
    has_client = jnp.zeros_like(already)
    for i in range(k_slots):
        has_client = has_client | (g2[f"rc{i}"] == op["client"])
    need = already & ~has_client
    g3 = _append_overlap(g2, need, op["client"], k_slots)
    want = jnp.where(displaced, prior, op["client"])
    landed = jnp.zeros_like(already)
    for i in range(k_slots):
        landed = landed | (g3[f"rc{i}"] == want)
    over = ln.any_lane((displaced | need) & ~landed)
    g3["overflow"] = st["overflow"] | over
    return g3


def _annotate_phase(st, op, enabled, view, a_slots, ln: Lanes):
    target = _range_targets(st, op, view) & enabled
    g = dict(st)
    over = ln.any_lane(target & (st[f"an{a_slots - 1}"] != -1))
    for i in range(a_slots - 1, 0, -1):
        g[f"an{i}"] = jnp.where(target, st[f"an{i - 1}"], st[f"an{i}"])
    g["an0"] = jnp.where(target, op["op_id"], st["an0"])
    g["overflow"] = st["overflow"] | over
    return g


def _ack_phase(st, op):
    kind = op["kind"]
    ins_hit = (kind == OpKind.ACK_INSERT) & \
        (st["ins_seq"] == DEV_UNASSIGNED) & \
        (st["local_seq"] == op["local_seq"])
    rem_hit = (kind == OpKind.ACK_REMOVE) & \
        (st["rem_seq"] == DEV_UNASSIGNED) & \
        (st["rem_local_seq"] == op["local_seq"])
    g = dict(st)
    g["ins_seq"] = jnp.where(ins_hit, op["seq"], st["ins_seq"])
    g["local_seq"] = jnp.where(ins_hit, 0, st["local_seq"])
    g["rem_seq"] = jnp.where(rem_hit, op["seq"], st["rem_seq"])
    g["rem_local_seq"] = jnp.where(rem_hit, 0, st["rem_local_seq"])
    return g


def _apply_one_batched(st, op, k_slots, a_slots, ln: Lanes,
                       with_runs=False):
    """kernel.apply_one with a leading doc axis; op fields are [B, 1]."""
    from .oppack import RUN_K

    kind = op["kind"]
    is_run = (kind == OpKind.INSERT_RUN) if with_runs else False
    is_edit = (kind == OpKind.INSERT) | (kind == OpKind.REMOVE) | \
        (kind == OpKind.ANNOTATE) | is_run
    is_range = (kind == OpKind.REMOVE) | (kind == OpKind.ANNOTATE)
    need = jnp.where(is_run, RUN_K + 1, 2) if with_runs else 2
    fits = st["count"] + need <= ln.total
    st = dict(st)
    st["overflow"] = st["overflow"] | (is_edit & ~fits)
    is_edit = is_edit & fits
    is_range = is_range & fits
    is_run = is_run & fits

    r, cl = op["ref_seq"], op["client"]
    s1 = _ensure_boundary(st, op["pos1"], r, cl, is_edit, k_slots, a_slots,
                          ln)
    s2 = _ensure_boundary(s1, op["pos2"], r, cl, is_range, k_slots, a_slots,
                          ln)
    view2 = _visibility(s2, r, cl, k_slots, ln)
    s_ins = _insert_phase(s2, op, is_edit & (kind == OpKind.INSERT), view2,
                          k_slots, a_slots, ln)
    if with_runs:
        s_ins = _insert_run_phase(s_ins, op, is_run, view2, k_slots,
                                  a_slots, ln)
    s_rem = _remove_phase(s_ins, op, is_range & (kind == OpKind.REMOVE),
                          view2, k_slots, ln)
    s_ann = _annotate_phase(s_rem, op, is_range & (kind == OpKind.ANNOTATE),
                            view2, a_slots, ln)
    out = _ack_phase(s_ann, op)

    acked = (kind != OpKind.NOOP) & (op["seq"] != DEV_UNASSIGNED)
    out["seq"] = jnp.where(acked, jnp.maximum(out["seq"], op["seq"]),
                           out["seq"])
    out["min_seq"] = jnp.where(acked, jnp.maximum(out["min_seq"], op["msn"]),
                               out["min_seq"])
    return out


# ---------------------------------------------------------------------------
# plane packing
# ---------------------------------------------------------------------------

_OP_FIELDS = PackedOps._fields


def op_cols(ops: PackedOps, runs):
    """Flatten PackedOps (+ optional RunCols) into named [..., T] columns:
    the INSERT_RUN sub columns (rl*/rs*/ri*) ride as extra per-step op
    scalars. Shared by the Pallas, jnp, and fused-sp drivers so the run
    layout has exactly one definition."""
    from .oppack import RUN_K

    fields = list(_OP_FIELDS)
    cols = {f: getattr(ops, f) for f in _OP_FIELDS}
    if runs is not None:
        for prefix, arr in (("rl", runs.length), ("rs", runs.seq),
                            ("ri", runs.op_id)):
            for i in range(RUN_K):
                name = f"{prefix}{i}"
                fields.append(name)
                cols[name] = arr[..., i]
    return fields, cols


def _to_planes(state: DocState):
    k = state.rem_clients.shape[-1]
    a = state.anno.shape[-1]
    b = state.length.shape[0]
    st = {name: getattr(state, name) for name in _SEG_PLANES}
    for i in range(k):
        st[f"rc{i}"] = state.rem_clients[..., i]
    for i in range(a):
        st[f"an{i}"] = state.anno[..., i]
    st["count"] = state.count.reshape(b, 1)
    st["min_seq"] = state.min_seq.reshape(b, 1)
    st["seq"] = state.seq.reshape(b, 1)
    st["overflow"] = state.overflow.reshape(b, 1)
    return st, k, a


def _from_planes(st, k, a) -> DocState:
    rem_clients = jnp.stack([st[f"rc{i}"] for i in range(k)], axis=-1)
    anno = jnp.stack([st[f"an{i}"] for i in range(a)], axis=-1)
    return DocState(
        **{name: st[name] for name in _SEG_PLANES
           if name in DocState._fields},
        rem_clients=rem_clients, anno=anno,
        count=st["count"][:, 0], min_seq=st["min_seq"][:, 0],
        seq=st["seq"][:, 0], overflow=st["overflow"][:, 0],
    )


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def _stream_loop(st, t_steps, get_op, k, a, ln: Lanes, with_runs=False):
    """Apply all T ops to the resident planes. get_op(t) fetches the op
    scalars as [B, 1] — from a value in the jnp driver, from the VMEM ref
    in the Pallas kernel (Mosaic supports dynamic slicing only on refs)."""

    def body(t, carry):
        return _apply_one_batched(carry, get_op(t), k, a, ln,
                                  with_runs=with_runs)

    return jax.lax.fori_loop(0, t_steps, body, st)


@jax.jit
# fluidlint: disable=MISSING_DONATE — non-donating: tests compare the
# kernel against this reference on the same input.
def apply_ops_fused_ref(state: DocState, ops: PackedOps) -> DocState:
    """jnp reference of the fused formulation (non-donating)."""
    st, k, a = _to_planes(state)
    fields, cols = op_cols(ops, None)

    def get_op(t):
        return {f: jax.lax.dynamic_slice_in_dim(cols[f], t, 1, axis=1)
                for f in fields}

    c = state.length.shape[-1]
    ln = local_lanes(c, lambda x, n: jnp.roll(x, n, axis=1))
    out = _stream_loop(st, ops.kind.shape[-1], get_op, k, a, ln)
    return _from_planes(out, k, a)


def _kernel(n_state: int, k: int, a: int, names, op3d: bool,
            op_fields=None, extract: bool = False):
    """Grid = (doc_tiles, T). The state planes' block index is constant in
    t, so Mosaic keeps them VMEM-resident across the whole op stream
    (revisited-block accumulator pattern); each grid step applies ONE op
    whose scalars arrive as [TILE, 1] blocks — no dynamic slicing.

    op_fields extends the per-step scalars with the INSERT_RUN sub
    columns (rl*/rs*/ri*) when run packing is active.

    extract adds four narrow outputs past the state planes — overflow
    (int16), count, min_seq, seq — written from the VMEM-resident result
    on the LAST op step, so the serving drain can read the narrow planes
    without a second extraction dispatch (the megakernel contract)."""
    op_fields = tuple(op_fields) if op_fields is not None else _OP_FIELDS
    with_runs = len(op_fields) > len(_OP_FIELDS)

    def kern(*refs):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        in_refs = refs[:n_state + len(op_fields)]
        out_refs = refs[n_state + len(op_fields):]
        t = pl.program_id(1)

        # The output VMEM window is NOT loaded from HBM on first visit —
        # seed it from the (buffer-aliased) input block explicitly. The
        # aliasing saves the HBM copy of the state, not this VMEM seed.
        @pl.when(t == 0)
        def _seed():
            for i in range(n_state):
                out_refs[i][:] = in_refs[i][:]

        st = {name: out_refs[i][:] for i, name in enumerate(names)}
        ln = local_lanes(st["length"].shape[-1],
                         lambda x, n: pltpu.roll(x, n, 1))
        # Op columns ride transposed (doc axis LAST, resident across t):
        # row t is a sublane slice (lane-dim dynamic slices must be
        # 128-aligned in Mosaic), transposed to the [TILE, 1] per-doc
        # scalar shape. At full 128-doc tiles the planes are [T, TILE];
        # narrower tiles ride [1, T, TILE] blocks (a [T, tile<128] lane
        # dim is not a legal block shape, but full-array dims always are).
        if op3d:
            op = {f: jnp.transpose(in_refs[n_state + i][0, pl.ds(t, 1), :])
                  for i, f in enumerate(op_fields)}
        else:
            op = {f: jnp.transpose(in_refs[n_state + i][pl.ds(t, 1), :])
                  for i, f in enumerate(op_fields)}
        out = _apply_one_batched(st, op, k, a, ln, with_runs=with_runs)
        for i, name in enumerate(names):
            out_refs[i][:] = out[name]

        if extract:
            ex = out_refs[n_state:]

            @pl.when(t == pl.num_programs(1) - 1)
            def _extract():
                ex[0][:] = out["overflow"].astype(jnp.int16)
                ex[1][:] = out["count"]
                ex[2][:] = out["min_seq"]
                ex[3][:] = out["seq"]
    return kern


def apply_ops_fused_pallas(state: DocState, ops: PackedOps,
                           interpret: bool = False,
                           runs=None, extract: bool = False):
    from jax.experimental import pallas as pl

    st, k, a = _to_planes(state)
    names = list(st.keys())
    b, c = state.length.shape
    t_steps = ops.kind.shape[-1]
    tile = tile_for_capacity(c)
    padded = ((b + tile - 1) // tile) * tile
    pad = padded - b

    def pad_rows(x):
        return jnp.pad(x, ((0, pad), (0, 0))) if pad else x

    st_in = [pad_rows(st[name]) for name in names]
    op_fields, cols = op_cols(ops, runs)
    op3d = tile < DOC_TILE
    if op3d:
        # [B, T] -> [n_tiles, T_pad, tile]: both trailing block dims equal
        # the array dims, the only always-legal shape at tile < 128.
        n_tiles = padded // tile
        t_pad = ((t_steps + 7) // 8) * 8
        op_in = [
            jnp.pad(pad_rows(cols[f]),
                    ((0, 0), (0, t_pad - t_steps)))
            .reshape(n_tiles, tile, t_pad).transpose(0, 2, 1)
            for f in op_fields]
        op_block = pl.BlockSpec((1, t_pad, tile), lambda i, t: (i, 0, 0))
    else:
        op_in = [pad_rows(cols[f]).T for f in op_fields]  # [T, B]
        op_block = pl.BlockSpec((t_steps, tile), lambda i, t: (0, i))

    def state_block(cols):
        return pl.BlockSpec((tile, cols), lambda i, t: (i, 0))

    grid = (padded // tile, t_steps)
    out_shapes = [jax.ShapeDtypeStruct((padded, x.shape[1]), x.dtype)
                  for x in st_in]
    if extract:
        # Narrow planes past the states: overflow(int16), count, min_seq,
        # seq — written in-kernel on the last op step (no aliasing; fresh
        # outputs).
        out_shapes = out_shapes + [
            jax.ShapeDtypeStruct((padded, 1), jnp.int16),
            jax.ShapeDtypeStruct((padded, 1), jnp.int32),
            jax.ShapeDtypeStruct((padded, 1), jnp.int32),
            jax.ShapeDtypeStruct((padded, 1), jnp.int32),
        ]
    out_specs = [state_block(x.shape[1]) for x in st_in]
    if extract:
        out_specs = out_specs + [state_block(1)] * 4
    outs = pl.pallas_call(
        _kernel(len(names), k, a, names, op3d, op_fields, extract=extract),
        out_shape=out_shapes,
        grid=grid,
        in_specs=[state_block(x.shape[1]) for x in st_in]
        + [op_block for _ in op_in],
        out_specs=out_specs,
        input_output_aliases={i: i for i in range(len(st_in))},
        interpret=interpret,
    )(*st_in, *op_in)
    result = {name: outs[i][:b] for i, name in enumerate(names)}
    out_state = _from_planes(result, k, a)
    if extract:
        narrow = tuple(outs[len(names) + i][:b, 0] for i in range(4))
        return out_state, narrow
    return out_state
