"""The TPU merge-tree kernel: vectorized op application over segment tables.

This replaces the reference's three hot loops (SURVEY.md §3: insertingWalk +
blockUpdatePathLengths, ackPendingSegment + zamboni, summary gather —
mergeTree.ts:2345,2770,1893,1422) with data-parallel array ops:

- position resolution: masked exclusive prefix sum of visible lengths under
  the op's (refSeq, clientId) perspective — no tree walk, no partial-length
  caches (the prefix sum IS the partial-length computation, fused);
- insert/split: roll-selects over the segment axis. TPU note: arbitrary
  data-dependent gathers lower to slow scatter/gather loops (~20x worse than
  shifts, measured); every structural change here is a shift-by-one, so it
  is expressed as where(j >= slot, roll(x, 1), x) — pure elementwise work
  the VPU streams at full bandwidth;
- the insert tie-break (mergeTree.ts:2248 breakTie): a vectorized first-true
  scan over the boundary run — skip acked tombstones, land before visible or
  concurrent-acked segments, skip unacked foreign segments;
- remove/annotate marking: masked column updates; annotates append into a
  fixed-depth per-segment ring of op ids (LWW-resolved host-side by seq;
  ring exhaustion sets the overflow flag instead of corrupting);
- zamboni compaction: keep-mask prefix sum + gather (runs between batches,
  not per op, so its gather cost amortizes).

One `step` applies one op to one document; `lax.scan` over the time axis x
`vmap` over the document axis yields the batched kernel that applies T ops
to B documents in one jit. All shapes are static; per-document streams are
NOOP-padded (oppack.py).

Semantics are conformance-tested against the scalar oracle
(tests/test_kernel.py) on randomized schedules, the same way the reference
farms assert convergence (SURVEY.md §4.2).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .constants import DEV_NO_REMOVE, DEV_UNASSIGNED
from .oppack import OpKind, PackedOps
from .state import DocState


# ---------------------------------------------------------------------------
# visibility
# ---------------------------------------------------------------------------

def _cumsum_sp(vlen: jnp.ndarray, sp_shards: int) -> jnp.ndarray:
    """Inclusive prefix sum over the capacity axis in the sequence-parallel
    formulation: sp_shards local cumsums + an exclusive scan of the shard
    totals (the two-level collective-scan recipe, parallel/seq_scan.py).
    With the capacity axis sharded over 'sp', the reshape aligns blocks to
    shards, the inner cumsum stays shard-local, and GSPMD lowers the tiny
    totals exchange to an all-gather over ICI — long-document position
    resolution scales across the mesh instead of serializing one chip."""
    c = vlen.shape[-1]
    if sp_shards <= 1 or c % sp_shards:
        return jnp.cumsum(vlen)
    blocks = vlen.reshape(sp_shards, c // sp_shards)
    local = jnp.cumsum(blocks, axis=-1)
    totals = local[:, -1]
    offsets = jnp.cumsum(totals) - totals  # exclusive over shards
    return (local + offsets[:, None]).reshape(c)


def visibility(s: DocState, ref_seq, client, sp_shards: int = 1
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(vis, vlen, cum): visibility mask, visible lengths, exclusive prefix
    sum at perspective (ref_seq, client). mergeTree.ts:1586 nodeLength."""
    c = s.capacity
    idx = jnp.arange(c, dtype=jnp.int32)
    valid = idx < s.count
    inserted = (s.ins_seq <= ref_seq) | (s.ins_client == client)
    removed = (s.rem_seq <= ref_seq) | jnp.any(
        s.rem_clients == client, axis=-1)
    vis = valid & inserted & ~removed
    vlen = jnp.where(vis, s.length, 0)
    cum = _cumsum_sp(vlen, sp_shards) - vlen  # exclusive
    return vis, vlen, cum


# ---------------------------------------------------------------------------
# shift helpers (roll-select: no data-dependent gathers on the hot path)
# ---------------------------------------------------------------------------

def _shift_right_at(s: DocState, slot, do) -> DocState:
    """Shift all segment rows at indices >= slot right by one (the row at
    slot duplicates its left neighbor, i.e. out[slot] == in[slot-1]) when
    `do`; identity otherwise. out[j] = in[j] for j < slot."""
    return _shift_right_by(s, slot, do, 1)


def _masked_scalar(values, mask):
    """values[argwhere(mask)] as a reduce (avoids dynamic_slice)."""
    return jnp.sum(jnp.where(mask, values, 0))


def _ensure_boundary(s: DocState, pos, ref_seq, client, enabled,
                     sp_shards: int = 1) -> DocState:
    """Split the segment containing `pos` (if any) so `pos` falls on a
    segment boundary (reference ensureIntervalBoundary, mergeTree.ts:2240)."""
    vis, vlen, cum = visibility(s, ref_seq, client, sp_shards)
    inside = vis & (cum < pos) & (pos < cum + vlen)
    do = enabled & jnp.any(inside)
    idx = jnp.argmax(inside).astype(jnp.int32)
    off = pos - _masked_scalar(cum, inside)
    parent_len = _masked_scalar(s.length, inside)
    g = _shift_right_at(s, idx + 1, do)
    j = jnp.arange(s.capacity, dtype=jnp.int32)
    is_left = do & (j == idx)
    is_right = do & (j == idx + 1)
    return g._replace(
        length=jnp.where(is_left, off,
                         jnp.where(is_right, parent_len - off, g.length)),
        origin_off=jnp.where(is_right, g.origin_off + off, g.origin_off),
    )


# ---------------------------------------------------------------------------
# op phases (single doc)
# ---------------------------------------------------------------------------

def _insert_phase(s: DocState, op: PackedOps, t, enabled, view) -> DocState:
    """Find the insert slot via the breakTie run-scan, shift, write the new
    segment (boundary already ensured, so the op never lands mid-segment).
    `view` is the precomputed visibility triple on `s` (shared with the
    range phases — one prefix sum serves both, see apply_one)."""
    r, cl, p = op.ref_seq[t], op.client[t], op.pos1[t]
    is_local = op.seq[t] == DEV_UNASSIGNED
    vis, vlen, cum = view
    c = s.capacity
    j = jnp.arange(c, dtype=jnp.int32)
    in_run = cum == p
    tomb = s.rem_seq <= r  # removed at-or-before refSeq: skip over
    acked_ins = s.ins_seq != DEV_UNASSIGNED
    stop = in_run & (vis | (~tomb & (is_local | acked_ins)) | (j >= s.count))
    # pos beyond the visible length leaves no stop slot: flag instead of
    # silently landing at argmax-of-all-false == 0.
    found = jnp.any(stop)
    bad = enabled & ~found
    enabled = enabled & found
    slot = jnp.argmax(stop).astype(jnp.int32)  # first stop
    g = _shift_right_at(s, slot, enabled)
    here = enabled & (j == slot)
    new_seq = op.seq[t]
    hereK = here[:, None]
    return g._replace(
        length=jnp.where(here, op.new_len[t], g.length),
        ins_seq=jnp.where(here, new_seq, g.ins_seq),
        ins_client=jnp.where(here, cl, g.ins_client),
        local_seq=jnp.where(here, jnp.where(is_local, op.local_seq[t], 0),
                            g.local_seq),
        rem_seq=jnp.where(here, DEV_NO_REMOVE, g.rem_seq),
        rem_local_seq=jnp.where(here, 0, g.rem_local_seq),
        rem_clients=jnp.where(hereK, -1, g.rem_clients),
        origin_op=jnp.where(here, op.op_id[t], g.origin_op),
        origin_off=jnp.where(here, 0, g.origin_off),
        anno=jnp.where(hereK, -1, g.anno),
        overflow=g.overflow | bad,
    )


def _shift_right_by(s: DocState, slot, do, k: int) -> DocState:
    """_shift_right_at generalized to a STATIC shift width k: rows at
    indices >= slot move right by k (rows [slot, slot+k) become stale
    copies — the caller overwrites all k); count grows by k."""
    c = s.capacity
    j = jnp.arange(c, dtype=jnp.int32)

    def shift(x):
        rolled = jnp.roll(x, k, axis=0)
        mask = (j >= slot) & do
        if x.ndim > 1:
            mask = mask.reshape(mask.shape + (1,) * (x.ndim - 1))
        return jnp.where(mask, rolled, x)

    return s._replace(
        length=shift(s.length),
        ins_seq=shift(s.ins_seq),
        ins_client=shift(s.ins_client),
        local_seq=shift(s.local_seq),
        rem_seq=shift(s.rem_seq),
        rem_local_seq=shift(s.rem_local_seq),
        rem_clients=shift(s.rem_clients),
        origin_op=shift(s.origin_op),
        origin_off=shift(s.origin_off),
        anno=shift(s.anno),
        count=s.count + do.astype(jnp.int32) * k,
    )


def _insert_run_phase(s: DocState, op: PackedOps, runs, t, enabled,
                      view) -> DocState:
    """INSERT_RUN (oppack.RUN_K packing): k cursor-advance inserts by one
    (client, refSeq) land as k contiguous rows at ONE tie-break slot —
    the slot the first insert's breakTie scan picks; each subsequent
    insert's scan provably lands immediately after its predecessor (its
    tie-run starts at the predecessor's right boundary, whose first stop
    row is the original target). One visibility pass + one static
    shift-by-K + K masked fills replace k full apply steps. Padding rows
    (length 0) are born dead (rem_seq 0): invisible at every perspective
    and zamboni'd by the next compact."""
    from .oppack import RUN_K

    r, cl, p = op.ref_seq[t], op.client[t], op.pos1[t]
    vis, vlen, cum = view
    c = s.capacity
    j = jnp.arange(c, dtype=jnp.int32)
    in_run = cum == p
    tomb = s.rem_seq <= r
    acked_ins = s.ins_seq != DEV_UNASSIGNED
    stop = in_run & (vis | (~tomb & acked_ins) | (j >= s.count))
    found = jnp.any(stop)
    bad = enabled & ~found
    enabled = enabled & found
    slot = jnp.argmax(stop).astype(jnp.int32)
    g = _shift_right_by(s, slot, enabled, RUN_K)
    rel = j - slot
    here = enabled & (rel >= 0) & (rel < RUN_K)

    def pick(col16, pad):
        # col16: [K] per-sub values; select by rel with K static terms.
        out = jnp.full((c,), pad, jnp.int32)
        for k in range(RUN_K):
            out = jnp.where(rel == k, col16[k], out)
        return out

    row_len = pick(runs.length[t], 0)
    row_seq = pick(runs.seq[t], 0)
    row_id = pick(runs.op_id[t], -1)
    live = here & (row_len > 0)
    dead = here & (row_len == 0)
    hereK = here[:, None]
    return g._replace(
        length=jnp.where(here, row_len, g.length),
        ins_seq=jnp.where(live, row_seq, jnp.where(dead, 0, g.ins_seq)),
        ins_client=jnp.where(live, cl, jnp.where(dead, -1, g.ins_client)),
        local_seq=jnp.where(here, 0, g.local_seq),
        rem_seq=jnp.where(live, DEV_NO_REMOVE,
                          jnp.where(dead, 0, g.rem_seq)),
        rem_local_seq=jnp.where(here, 0, g.rem_local_seq),
        rem_clients=jnp.where(hereK, -1, g.rem_clients),
        origin_op=jnp.where(here, row_id, g.origin_op),
        origin_off=jnp.where(here, 0, g.origin_off),
        anno=jnp.where(hereK, -1, g.anno),
        overflow=g.overflow | bad,
    )


def _range_targets(s: DocState, op: PackedOps, t, view):
    """Visible segments fully inside [pos1, pos2) (boundaries pre-split).
    `view` is the shared visibility triple (see apply_one)."""
    vis, vlen, cum = view
    return vis & (vlen > 0) & (cum >= op.pos1[t]) & (cum + vlen <= op.pos2[t])


def _remove_phase(s: DocState, op: PackedOps, t, enabled, view) -> DocState:
    """markRangeRemoved semantics (mergeTree.ts:2607): first acked remove
    wins; a pending local remove is overwritten by an acked one (prior
    remover becomes an overlap client); later removers are overlap clients."""
    target = _range_targets(s, op, t, view) & enabled
    cl, seq = op.client[t], op.seq[t]
    is_local = seq == DEV_UNASSIGNED
    fresh = target & (s.rem_seq == DEV_NO_REMOVE)
    pend_overwrite = target & (s.rem_seq == DEV_UNASSIGNED) & ~is_local
    already = target & (s.rem_seq != DEV_NO_REMOVE) & ~pend_overwrite

    rem_seq = jnp.where(fresh, jnp.where(is_local, DEV_UNASSIGNED, seq),
                        jnp.where(pend_overwrite, seq, s.rem_seq))
    rem_local_seq = jnp.where(fresh & is_local, op.local_seq[t],
                              jnp.where(pend_overwrite, 0, s.rem_local_seq))

    k = s.rem_clients.shape[-1]
    rc = s.rem_clients
    # fresh: primary slot takes this client.
    rc = jnp.where(fresh[:, None] & (jnp.arange(k) == 0), cl, rc)
    # pend_overwrite: prior (pending) remover shifts into an overlap slot,
    # the acked remover takes the primary slot.
    prior = s.rem_clients[:, 0]
    rc = jnp.where(pend_overwrite[:, None] & (jnp.arange(k) == 0), cl, rc)
    displaced = pend_overwrite & (prior != cl)
    rc = _append_overlap(rc, displaced, prior)
    # already-removed (acked): record this client as an overlapping remover.
    need = already & ~jnp.any(s.rem_clients == cl, axis=-1)
    rc = _append_overlap(rc, need, jnp.full_like(prior, 0) + cl)
    overflow = jnp.any((displaced | need) & ~jnp.any(rc == jnp.where(
        displaced, prior, cl)[:, None], axis=-1))
    return s._replace(rem_seq=rem_seq, rem_local_seq=rem_local_seq,
                      rem_clients=rc, overflow=s.overflow | overflow)


def _append_overlap(rc: jnp.ndarray, need: jnp.ndarray,
                    client: jnp.ndarray) -> jnp.ndarray:
    """Per-row: place client[i] into the first free (-1) overlap slot (>=1)
    where need[i]. Static K loop, K = MAX_OVERLAP_CLIENTS."""
    k = rc.shape[-1]
    free = rc == -1
    free = free.at[:, 0].set(False)  # slot 0 is the primary remover
    first_free = jnp.argmax(free, axis=-1)  # 0 if none free (masked below)
    can = need & jnp.any(free, axis=-1)
    onehot = jnp.arange(k) == first_free[:, None]
    return jnp.where((can[:, None]) & onehot, client[:, None], rc)


def _annotate_phase(s: DocState, op: PackedOps, t, enabled, view) -> DocState:
    """Push the annotate op id into each affected segment's fixed-depth ring
    (newest first); host resolves per-key LWW by op seq at summary time.
    Ring exhaustion (oldest id still occupied) flags overflow."""
    target = _range_targets(s, op, t, view) & enabled
    tK = target[:, None]
    pushed = jnp.concatenate(
        [jnp.full(s.anno.shape[:-1] + (1,), op.op_id[t], jnp.int32),
         s.anno[..., :-1]], axis=-1)
    overflow = jnp.any(target & (s.anno[..., -1] != -1))
    return s._replace(anno=jnp.where(tK, pushed, s.anno),
                      overflow=s.overflow | overflow)


def _ack_phase(s: DocState, op: PackedOps, t, kind) -> DocState:
    """Assign the server seq to pending segments matching the acked local op
    (reference ackPendingSegment, mergeTree.ts:1893). An overwritten pending
    remove keeps the earlier remote seq (segment.ack returning false)."""
    seq, target = op.seq[t], op.local_seq[t]
    ins_hit = (kind == OpKind.ACK_INSERT) & (s.ins_seq == DEV_UNASSIGNED) & \
        (s.local_seq == target)
    rem_hit = (kind == OpKind.ACK_REMOVE) & (s.rem_seq == DEV_UNASSIGNED) & \
        (s.rem_local_seq == target)
    return s._replace(
        ins_seq=jnp.where(ins_hit, seq, s.ins_seq),
        local_seq=jnp.where(ins_hit, 0, s.local_seq),
        rem_seq=jnp.where(rem_hit, seq, s.rem_seq),
        rem_local_seq=jnp.where(rem_hit, 0, s.rem_local_seq),
    )


# ---------------------------------------------------------------------------
# one step
# ---------------------------------------------------------------------------

def apply_one(s: DocState, op: PackedOps, t, sp_shards: int = 1,
              runs=None) -> DocState:
    """Apply op column t to a single document's state."""
    from .oppack import RUN_K

    kind = op.kind[t]
    is_run = (kind == OpKind.INSERT_RUN) if runs is not None else False
    is_edit = (kind == OpKind.INSERT) | (kind == OpKind.REMOVE) | \
        (kind == OpKind.ANNOTATE) | is_run
    is_range = (kind == OpKind.REMOVE) | (kind == OpKind.ANNOTATE)
    # Capacity guard: an edit may create up to 2 new slots (an insert run
    # up to RUN_K + 1). Overflowing ops become no-ops with the overflow
    # flag set; the host re-runs that doc at higher capacity.
    need = jnp.where(is_run, RUN_K + 1, 2) if runs is not None else 2
    fits = s.count + need <= s.capacity
    s = s._replace(overflow=s.overflow | (is_edit & ~fits))
    is_edit = is_edit & fits
    is_range = is_range & fits
    is_run = is_run & fits

    r, cl = op.ref_seq[t], op.client[t]
    s1 = _ensure_boundary(s, op.pos1[t], r, cl, is_edit, sp_shards)
    s2 = _ensure_boundary(s1, op.pos2[t], r, cl, is_range, sp_shards)

    # One visibility pass on s2 serves the insert AND range phases: an
    # INSERT leaves the range phases disabled and a REMOVE/ANNOTATE leaves
    # the insert phase disabled (s_ins == s2 exactly), so the shared view
    # is valid wherever it is consumed — 3 prefix sums per op, not 4.
    view2 = visibility(s2, r, cl, sp_shards)
    s_ins = _insert_phase(s2, op, t, is_edit & (kind == OpKind.INSERT),
                          view2)
    if runs is not None:
        s_ins = _insert_run_phase(s_ins, op, runs, t, is_run, view2)
    s_rem = _remove_phase(s_ins, op, t, is_range & (kind == OpKind.REMOVE),
                          view2)
    s_ann = _annotate_phase(s_rem, op, t,
                            is_range & (kind == OpKind.ANNOTATE), view2)
    out = _ack_phase(s_ann, op, t, kind)

    # Pending local submits (seq == DEV_UNASSIGNED) must not advance the
    # acked high-water mark used as the default extraction perspective.
    acked = (kind != OpKind.NOOP) & (op.seq[t] != DEV_UNASSIGNED)
    out = out._replace(
        seq=jnp.where(acked, jnp.maximum(out.seq, op.seq[t]), out.seq),
        min_seq=jnp.where(acked, jnp.maximum(out.min_seq, op.msn[t]),
                          out.min_seq),
    )
    return out


# The phases are written against single-doc shapes; vmap lifts them over the
# document batch axis, scan drives the time axis.

def _scan_ops(state: DocState, ops: PackedOps, batched: bool,
              sp_shards: int = 1, runs=None) -> DocState:
    steps = ops.steps

    def body(s, t):
        if batched:
            if runs is not None:
                s2 = jax.vmap(lambda sd, od, rd: apply_one(
                    sd, od, t, sp_shards, runs=rd))(s, ops, runs)
            else:
                s2 = jax.vmap(lambda sd, od: apply_one(sd, od, t, sp_shards)
                              )(s, ops)
        else:
            s2 = apply_one(s, ops, t, sp_shards, runs=runs)
        return s2, None

    out, _ = jax.lax.scan(body, state, jnp.arange(steps, dtype=jnp.int32))
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_ops(state: DocState, ops: PackedOps) -> DocState:
    """Apply a [T] op stream to a single document."""
    return _scan_ops(state, ops, batched=False)


@functools.partial(jax.jit, donate_argnums=(0,))
def apply_ops_batched(state: DocState, ops: PackedOps) -> DocState:
    """Apply [B, T] op streams to B documents: scan(T) of vmap(B)."""
    return _scan_ops(state, ops, batched=True)


# Non-donating variants for callers that must retain the pre-apply state
# (overflow recovery / bulk catch-up retry at a larger capacity): jax arrays
# are immutable, so keeping the input alive costs nothing extra.
@jax.jit
# fluidlint: disable=MISSING_DONATE — non-donating by design (see comment
# above): overflow recovery re-applies from the retained input.
def apply_ops_keep(state: DocState, ops: PackedOps, runs=None) -> DocState:
    return _scan_ops(state, ops, batched=False, runs=runs)


@jax.jit
# fluidlint: disable=MISSING_DONATE — non-donating by design (see comment
# above): overflow recovery re-applies from the retained input.
def apply_ops_batched_keep(state: DocState, ops: PackedOps) -> DocState:
    return _scan_ops(state, ops, batched=True)


def apply_if_any(apply_fn, state: DocState, active) -> DocState:
    """lax.cond-guard an apply inside a larger traced program: run
    ``apply_fn(state)`` when ``active`` (any real op in the block), else
    return ``state`` unchanged.

    This is the megakernel scan's padding shortcut
    (serve_step.serve_megakernel): stacking K serving windows into one
    scanned program pads every window to the union of staged LWW
    buckets, so a window that staged nothing for a bucket carries an
    all-NOOP op plane there — and a NOOP stream is an exact identity on
    the lane state (every phase masks on the op kind; locked by the ring
    bit-identity tests), so skipping
    the T-step apply is free correctness-wise and saves the full
    scan-kernel cost of the padded window. NOT jitted here: it traces
    inside the caller's program (the scan body)."""
    return jax.lax.cond(active, apply_fn, lambda s: s, state)


# ---------------------------------------------------------------------------
# zamboni: compaction
# ---------------------------------------------------------------------------

def _gather_segments(s: DocState, src: jnp.ndarray) -> DocState:
    """Reindex all segment columns by src (clipped gather). Only used off
    the per-op hot path (compaction), where the arbitrary-gather cost
    amortizes over a whole batch of applied ops."""
    src = jnp.clip(src, 0, s.capacity - 1)
    return s._replace(
        length=s.length[src],
        ins_seq=s.ins_seq[src],
        ins_client=s.ins_client[src],
        local_seq=s.local_seq[src],
        rem_seq=s.rem_seq[src],
        rem_local_seq=s.rem_local_seq[src],
        rem_clients=s.rem_clients[src],
        origin_op=s.origin_op[src],
        origin_off=s.origin_off[src],
        anno=s.anno[src],
    )


def _pack_src(s: DocState):
    """The keep-mask + prefix-sum + scatter-to-gather addressing SHARED by
    zamboni compaction and snapshot extraction: both left-pack exactly the
    not-yet-zambonied rows (everything not removed at-or-before min_seq).
    Returns (src, n): gather sources per output slot and the live count."""
    c = s.capacity
    idx = jnp.arange(c, dtype=jnp.int32)
    valid = idx < s.count
    keep = valid & ~(s.rem_seq <= s.min_seq)
    n = jnp.sum(keep.astype(jnp.int32))
    # Destination of each kept row; gather formulation: for each output slot
    # j, source = index of the (j+1)-th kept row.
    order = jnp.cumsum(keep.astype(jnp.int32)) - 1  # dest slot per kept row
    src = jnp.full((c,), c - 1, jnp.int32)
    src = src.at[jnp.where(keep, order, c)].set(idx, mode="drop")
    return src, n


def _compact_one(s: DocState) -> DocState:
    """Free segments removed at-or-before min_seq (reference zamboni,
    mergeTree.ts:1422): stable-partition live segments to the front."""
    c = s.capacity
    src, new_count = _pack_src(s)
    g = _gather_segments(s, src)
    pad = jnp.arange(c) >= new_count
    g = g._replace(
        length=jnp.where(pad, 0, g.length),
        ins_seq=jnp.where(pad, DEV_UNASSIGNED, g.ins_seq),
        ins_client=jnp.where(pad, -1, g.ins_client),
        local_seq=jnp.where(pad, 0, g.local_seq),
        rem_seq=jnp.where(pad, DEV_NO_REMOVE, g.rem_seq),
        rem_local_seq=jnp.where(pad, 0, g.rem_local_seq),
        rem_clients=jnp.where(pad[:, None], -1, g.rem_clients),
        origin_op=jnp.where(pad, -1, g.origin_op),
        origin_off=jnp.where(pad, 0, g.origin_off),
        anno=jnp.where(pad[:, None], -1, g.anno),
        count=new_count,
    )
    return g


@jax.jit
def compact(state: DocState) -> DocState:
    return _compact_one(state)


@jax.jit
def compact_batched(state: DocState) -> DocState:
    return jax.vmap(_compact_one)(state)


# ---------------------------------------------------------------------------
# paged lane memory: gather/scatter-by-page-id (mergetree/paging.py)
# ---------------------------------------------------------------------------

def gather_pages(pool: DocState, page_ids: jnp.ndarray, counts, min_seqs,
                 seqs) -> DocState:
    """Materialize a batch of documents from their pages: ``page_ids``
    is the [B, P] int32 page-table plane (-1 pads short tables and
    gathers the reserved blank page 0, so padded rows are canonical
    blank padding), ``pool`` the [n_pages, PAGE_ROWS, ...] page pool.
    Returns a [B, P*PAGE_ROWS, ...] DocState view — the SAME shape the
    bucketed apply consumes, so every op phase runs unchanged on it —
    with per-doc scalars injected from the host mirrors and a fresh
    overflow plane. The gather is by page id only: a document's rows
    never move on growth, they just gain pages."""
    gidx = jnp.maximum(page_ids, 0)
    b, p = page_ids.shape
    r = pool.capacity

    def g(col):
        x = col[gidx]  # [B, P, R, ...]
        return x.reshape((b, p * r) + x.shape[3:])

    return DocState(
        length=g(pool.length), ins_seq=g(pool.ins_seq),
        ins_client=g(pool.ins_client), local_seq=g(pool.local_seq),
        rem_seq=g(pool.rem_seq), rem_local_seq=g(pool.rem_local_seq),
        rem_clients=g(pool.rem_clients), origin_op=g(pool.origin_op),
        origin_off=g(pool.origin_off), anno=g(pool.anno),
        count=counts, min_seq=min_seqs, seq=seqs,
        overflow=jnp.zeros((b,), jnp.bool_),
    )


def scatter_pages(pool: DocState, page_ids: jnp.ndarray,
                  view: DocState) -> DocState:
    """Write a [B, P*PAGE_ROWS, ...] view back into its pages. Padding
    slots (page id -1) redirect out of bounds and DROP — callers
    guarantee live rows never spill into padding pages (counts <=
    allocated rows, asserted host-side by PagedMergeStore), so dropped
    rows are always blank. Each real page has exactly one owner, so the
    scatter is collision-free."""
    b, p = page_ids.shape
    r = pool.capacity
    n = pool.length.shape[0]
    dst = jnp.where(page_ids >= 0, page_ids, n)  # OOB -> mode="drop"

    def s(col, v):
        vp = v.reshape((b, p, r) + v.shape[2:])
        return col.at[dst].set(vp, mode="drop")

    return pool._replace(
        length=s(pool.length, view.length),
        ins_seq=s(pool.ins_seq, view.ins_seq),
        ins_client=s(pool.ins_client, view.ins_client),
        local_seq=s(pool.local_seq, view.local_seq),
        rem_seq=s(pool.rem_seq, view.rem_seq),
        rem_local_seq=s(pool.rem_local_seq, view.rem_local_seq),
        rem_clients=s(pool.rem_clients, view.rem_clients),
        origin_op=s(pool.origin_op, view.origin_op),
        origin_off=s(pool.origin_off, view.origin_off),
        anno=s(pool.anno, view.anno),
    )


def paged_stats_vec(ops: PackedOps, out: DocState) -> jnp.ndarray:
    """The paged apply's device telemetry plane (telemetry/device_stats
    PAGED_SLOTS order): staged ops by kind, flagged docs, post-apply
    live rows — counted inside the program, so the host learns the
    group's facts from the readback it already pays. Padding rows
    (all-NOOP streams on blank views, zeroed counts) contribute
    nothing, so host mirrors reconcile exactly."""
    from .oppack import OpKind as K

    per_kind = [jnp.sum((ops.kind == kv).astype(jnp.int32))
                for kv in (K.INSERT, K.REMOVE, K.ANNOTATE, K.ACK_INSERT,
                           K.ACK_REMOVE, K.INSERT_RUN)]
    return jnp.stack(per_kind + [
        jnp.sum(out.overflow.astype(jnp.int32)),
        jnp.sum(out.count.astype(jnp.int32)),
    ])


@functools.partial(jax.jit, donate_argnums=(0, 1),
                   static_argnames=("stats",))
def apply_ops_paged(pool: DocState, page_ids: jnp.ndarray, counts,
                    min_seqs, seqs, ops: PackedOps, stats: bool = False):
    """One [B, T] op window over paged documents: gather-by-page-id ->
    the unchanged batched apply -> scatter-by-page-id, in ONE jitted
    dispatch with the page pool and page-table plane DONATED (the pool
    updates in place; page_ids alias straight through to the returned
    plane). Returns (pool', page_ids, count, min_seq, seq, overflow,
    pre_view): pre_view is the gathered PRE-window group — the rollback
    the rare unpredicted-overflow recovery (annotate-ring/overlap-slot
    exhaustion) scatters back for flagged docs only, so donation costs
    one group-view allocation instead of a whole retained pool.
    ``stats`` (static) appends the device telemetry plane
    (paged_stats_vec) as one more element — same dispatch, no extra
    program, bit-identical lane results either way."""
    pre = gather_pages(pool, page_ids, counts, min_seqs, seqs)
    out = _scan_ops(pre, ops, batched=True)
    pool2 = scatter_pages(pool, page_ids, out)
    base = (pool2, page_ids, out.count, out.min_seq, out.seq,
            out.overflow, pre)
    if stats:
        return base + (paged_stats_vec(ops, out),)
    return base


@functools.partial(jax.jit, donate_argnums=(0,))
def rollback_pages(pool: DocState, page_ids: jnp.ndarray,
                   pre: DocState) -> DocState:
    """Scatter a retained pre-window view back over flagged docs' pages
    (page_ids here is the FLAGGED sub-plane): the paged overflow
    recovery's rollback half."""
    return scatter_pages(pool, page_ids, pre)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def compact_pages(pool: DocState, page_ids: jnp.ndarray, counts,
                  min_seqs, seqs):
    """Page-granular zamboni for a (budgeted) group of fragmented docs:
    gather -> left-pack compact -> scatter. The caller releases pages
    wholly past the returned counts (PagedMergeStore.release_trailing)
    — compaction is how a shrinking document actually gives pages
    back."""
    view = gather_pages(pool, page_ids, counts, min_seqs, seqs)
    g = jax.vmap(_compact_one)(view)
    return scatter_pages(pool, page_ids, g), page_ids, g.count


@functools.partial(jax.jit, donate_argnums=(0, 1))
def compact_extract_paged(pool: DocState, page_ids: jnp.ndarray, counts,
                          min_seqs, seqs):
    """Fused zamboni + snapshot extraction over gathered page views (the
    paged analog of compact_extract_batched): ONE dispatch returns the
    compacted pool (adopted in place — pool donated) plus packed
    per-doc rows in the extract_visible_batched layout, so host
    assembly (host.assemble_snapshot) runs unchanged."""
    view = gather_pages(pool, page_ids, counts, min_seqs, seqs)
    g, packed = jax.vmap(_compact_extract_one)(view)
    return scatter_pages(pool, page_ids, g), page_ids, g.count, packed


# Non-donating variants of every paged-pool entry point, for MESH-placed
# pools: donating a dp-sharded plane through the persistent XLA compile
# cache corrupts it on warm reload (jax 0.4.37 — docs/serving_pipeline.md
# R6, lint-enforced by MESH_DONATION_GATE). PagedMergeStore selects the
# dispatch once at construction (donate = mesh is None); the single-chip
# path keeps the donated fast forms above.
apply_ops_paged_keep = functools.partial(
    jax.jit, static_argnames=("stats",))(apply_ops_paged.__wrapped__)
rollback_pages_keep = jax.jit(rollback_pages.__wrapped__)
compact_pages_keep = jax.jit(compact_pages.__wrapped__)
compact_extract_paged_keep = jax.jit(compact_extract_paged.__wrapped__)


# ---------------------------------------------------------------------------
# batched summary extraction
# ---------------------------------------------------------------------------

def _extract_one(s: DocState):
    """Left-pack the snapshot-relevant segment rows — everything not yet
    zambonied (removed at-or-before min_seq), i.e. visible text PLUS
    contended collab-window metadata — via mask + prefix-sum addressing
    into a dense output, so the host reads exactly the live rows instead
    of scanning the whole capacity (reference snapshotV1.ts:33 segment
    gather via mapRange, batched; the snapshot stays loadable mid-window)."""
    src, n = _pack_src(s)
    return (s.origin_op[src], s.origin_off[src], s.length[src],
            s.anno[src], s.ins_seq[src], s.ins_client[src],
            s.rem_seq[src], s.rem_clients[src, 0], n)


def _compact_extract_one(s: DocState):
    """Fused zamboni + extraction: ONE keep-mask/prefix-sum/gather serves
    both the compacted next state and the packed snapshot rows (they are
    the same left-pack — extraction keeps exactly what compaction keeps),
    so a summarize pass pays one device program instead of two and the
    packed rows are post-GC minimal. Extraction columns read from the
    compacted rows, so padding slots carry clean blanks, not stale data."""
    g = _compact_one(s)
    return g, (g.origin_op, g.origin_off, g.length, g.anno, g.ins_seq,
               g.ins_client, g.rem_seq, g.rem_clients[:, 0], g.count)


@jax.jit
def extract_visible_batched(state: DocState):
    """One device pass over a [B, ...] batch -> packed per-doc segment
    rows: (origin_op, origin_off, length, anno, ins_seq, ins_client,
    rem_seq, rem_client) each [B, C] (rows >= counts[b] are padding) +
    counts [B]. One D2H transfer serves every document's snapshot
    assembly."""
    return jax.vmap(_extract_one)(state)


@functools.partial(jax.jit, static_argnames=("stats",))
# fluidlint: disable=MISSING_DONATE — non-donating by design: the serving
# extract path retains the pre-compaction bucket state until the caller
# adopts the compacted result (mirrors the *_keep apply family).
def compact_extract_batched(state: DocState, stats: bool = False):
    """Fused zamboni + snapshot extraction over a [B, ...] batch: returns
    (compacted_state, packed) from ONE jitted dispatch. `packed` has the
    extract_visible_batched layout; `compacted_state` is the post-GC state
    the caller may adopt in place of the input (bit-identical to
    compact_batched(state), locked by tests/test_narrow_wire.py).

    ``stats`` (static) appends the PRE-compaction per-doc live-row
    counts as a third element: the host derives zamboni reclamation
    (pre minus post counts) from the dispatch it already pays — the
    pre counts are device-resident, so without this plane the fact
    would cost a separate fetch. Results are bit-identical either way
    (the plane is a pure extra output)."""
    out = jax.vmap(_compact_extract_one)(state)
    if stats:
        return out + (state.count.astype(jnp.int32),)
    return out


def _gather_rows(state, idx):
    return jax.tree_util.tree_map(
        lambda x: x[idx] if getattr(x, "ndim", 0) else x, state)


# Probed: the dirty-lane sub-batch gather must NOT recompile per distinct
# dirty count — gather_rows_pow2 pads the index vector to a power of two
# precisely so the compiled variants stay bounded at log2(B). The probe
# (telemetry.counters.JitRetraceProbe) counts cache growth as
# kernel.extract_gather.* and feeds kernel.retrace_count; the regression
# lock is tests/test_narrow_wire.py::TestGatherRowsPow2.
_gather_rows_jit = None


def pad_pow2_indices(rows):
    """Host ints -> (int32 index vector zero-padded to the next power of
    two, real count). The pow2 pad is THE retrace bound for every
    dynamic-count gather on the summarize path: the jit cache holds
    log2(B) variants instead of one per distinct dirty count."""
    import numpy as np

    idx = np.asarray(rows, np.int32).reshape(-1)
    n = idx.size
    n_pad = 1 << max(n - 1, 0).bit_length()
    idx_p = np.zeros(n_pad, np.int32)
    idx_p[:n] = idx
    return idx_p, n


def gather_rows_pow2(state, rows):
    """Gather batch rows `rows` (host ints) of a [B, ...] state tree into
    a power-of-two-padded sub-batch (padding repeats row 0 — callers index
    only the first len(rows) rows). Returns (sub_state, n). The pow2 pad
    bounds the jit cache at log2(B) variants instead of one per distinct
    dirty count (the retrace hazard bench.py's extract_dirty used to
    carry)."""
    global _gather_rows_jit
    if _gather_rows_jit is None:
        from ..telemetry.counters import JitRetraceProbe
        _gather_rows_jit = JitRetraceProbe(jax.jit(_gather_rows),
                                           name="kernel.extract_gather")
    idx_p, n = pad_pow2_indices(rows)
    return _gather_rows_jit(state, jnp.asarray(idx_p)), n


@functools.partial(jax.jit, static_argnums=1)
def _slice_stack(cols, mx):
    return jnp.stack([c[:, :mx] for c in cols])


@functools.partial(jax.jit, static_argnums=1)
def _slice_rows(x, mx):
    return x[:, :mx]


# Narrow-wire bound: deltas/values above this fall back to the exact
# int32 plane refetch for the overflowing docs (headroom under int16 max
# mirrors serve_step's 32000 msn-delta cutoff).
_NARROW_MAX = 32000


@functools.partial(jax.jit, static_argnums=1)
def _narrow_pack(packed, mx):
    """Device-side narrow delta packing of an extraction result: the
    bounded columns (length, origin_off, ins_client, rem_client, anno id
    deltas) ride int16, the seq columns delta-encode against a per-doc
    base (min live value — within one collab window deltas are small)
    with sentinel codes for pending/no-remove, and a per-doc ok bit
    flags any doc whose values escape the narrow range (the host then
    refetches that doc's exact int32 planes — the same trick as
    serve_step's int16 window results). origin_op stays int32: payload
    ids are unbounded and not seq-shaped. Cuts extraction D2H bytes
    roughly in half (asserted by tests/test_narrow_wire.py)."""
    (origin_op, origin_off, length, anno, ins_seq, ins_client,
     rem_seq, rem_client, counts) = packed

    def sl(x):
        return x[:, :mx]

    length, origin_off = sl(length), sl(origin_off)
    ins_seq, ins_client = sl(ins_seq), sl(ins_client)
    rem_seq, rem_client = sl(rem_seq), sl(rem_client)
    op32 = sl(origin_op)
    anno_m = anno[:, :mx, :]
    j = jnp.arange(mx, dtype=jnp.int32)
    live = j[None, :] < counts[:, None]
    big = jnp.int32(1 << 30)

    ins_acked = live & (ins_seq != DEV_UNASSIGNED)
    base_ins = jnp.min(jnp.where(ins_acked, ins_seq, big), axis=1)
    base_ins = jnp.where(base_ins == big, 0, base_ins)
    d_ins = jnp.where(ins_acked, ins_seq - base_ins[:, None], -1)

    rem_real = live & (rem_seq != DEV_NO_REMOVE) & \
        (rem_seq != DEV_UNASSIGNED)
    base_rem = jnp.min(jnp.where(rem_real, rem_seq, big), axis=1)
    base_rem = jnp.where(base_rem == big, 0, base_rem)
    d_rem = jnp.where(rem_real, rem_seq - base_rem[:, None],
                      jnp.where(live & (rem_seq == DEV_UNASSIGNED), -2, -1))

    anno_live = live[:, :, None] & (anno_m >= 0)
    base_anno = jnp.min(jnp.where(anno_live, anno_m, big), axis=(1, 2))
    base_anno = jnp.where(base_anno == big, 0, base_anno)
    d_anno = jnp.where(anno_live, anno_m - base_anno[:, None, None], -1)

    def in_range(x, m):
        masked = jnp.where(m, x, 0)
        axes = tuple(range(1, x.ndim))
        return jnp.all((masked >= -2) & (masked <= _NARROW_MAX), axis=axes)

    ok = (in_range(length, live) & in_range(origin_off, live)
          & in_range(ins_client, live) & in_range(rem_client, live)
          & in_range(d_ins, live) & in_range(d_rem, live)
          & in_range(d_anno, anno_live))

    def n16(x):
        # fluidlint: disable=DTYPE_DRIFT — deliberate narrow wire packing
        # (host decodes back to int32; overflow guarded by the ok bit).
        return jnp.clip(x, -(1 << 15), (1 << 15) - 1).astype(jnp.int16)

    stacked16 = jnp.stack([
        n16(jnp.where(live, length, 0)),
        n16(jnp.where(live, origin_off, 0)),
        n16(jnp.where(live, ins_client, -1)),
        n16(jnp.where(live, rem_client, -1)),
        n16(d_ins), n16(d_rem)])
    meta = jnp.stack([base_ins, base_rem, base_anno,
                      ok.astype(jnp.int32)])
    return stacked16, n16(d_anno), op32, meta


@functools.partial(jax.jit, static_argnums=2)
def _exact_rows(packed, idx, mx):
    """Exact int32 planes for the (rare) docs whose values escape the
    narrow range: one stacked gather per refetch, idx pow2-padded by the
    caller so the compiled variants stay bounded."""
    (origin_op, origin_off, length, anno, ins_seq, ins_client,
     rem_seq, rem_client, _counts) = packed

    def take(x):
        return x[idx, :mx]

    return (jnp.stack([take(origin_op), take(origin_off), take(length),
                       take(ins_seq), take(ins_client), take(rem_seq),
                       take(rem_client)]), anno[idx, :mx, :])


def fetch_extracted(packed, narrow: bool = True) -> tuple:
    """Host fetch of an extraction result, sliced to the batch's max live
    row count BEFORE the transfer: with left-packed rows everything past
    max(counts) is padding, so this cuts D2H bytes by C/max_count — and
    same-shaped columns ride ONE stacked transfer, because per-array
    transfer overhead, not bandwidth, dominates small fetches. The slice width buckets to a multiple of
    32 so the jitted slice/stack programs cache across calls (up to
    capacity/32 variants — counts drift slowly, so in practice a handful;
    tighter than power-of-two slicing by up to 37% of the bytes).

    narrow=True (default) additionally rides the bounded columns as int16
    and delta-encodes the seq columns per doc (_narrow_pack), decoding
    back to the EXACT int32 arrays host-side — callers see bit-identical
    results either way; only the D2H bytes change (~2x fewer). Docs whose
    values escape int16 refetch their exact planes (counted as
    summarize.wire_refetch). Total transferred bytes accumulate in the
    summarize.bytes_d2h counter."""
    import numpy as np

    from ..telemetry import counters as _counters

    counts = np.asarray(packed[-1])
    mx = max(int(counts.max()) if counts.size else 0, 1)
    capacity = packed[0].shape[1]
    # Bucket the slice width to a multiple of 32: bounded jit-cache
    # variants without inflating the transfer much beyond max(counts).
    mx = min(((mx + 31) // 32) * 32, capacity)
    nbytes = counts.nbytes

    if not narrow:
        cols = packed[:-1]
        # Group stackable columns: same (ndim, dtype) 2-D planes stack
        # into one [n, B, mx] transfer; anything else (3-D anno) alone.
        by_kind = {}
        for i, x in enumerate(cols):
            key = (x.ndim, str(x.dtype)) if x.ndim == 2 else ("solo", i)
            by_kind.setdefault(key, []).append(i)
        fetched: dict = {}
        for key, idxs in by_kind.items():
            if key[0] == 2 and len(idxs) > 1:
                arr = np.asarray(_slice_stack(
                    tuple(cols[i] for i in idxs), mx))
                nbytes += arr.nbytes
                for j, i in enumerate(idxs):
                    fetched[i] = arr[j]
            else:
                for i in idxs:
                    fetched[i] = np.asarray(_slice_rows(cols[i], mx))
                    nbytes += fetched[i].nbytes
        _counters.increment("summarize.bytes_d2h", nbytes)
        return tuple(fetched[i] for i in range(len(cols))) + (counts,)

    stacked16, anno16, op32, meta = _narrow_pack(packed, mx)
    s16 = np.asarray(stacked16)
    a16 = np.asarray(anno16)
    op32 = np.asarray(op32)
    meta = np.asarray(meta)
    nbytes += s16.nbytes + a16.nbytes + op32.nbytes + meta.nbytes
    base_ins, base_rem, base_anno, ok = meta

    length = s16[0].astype(np.int32)
    origin_off = s16[1].astype(np.int32)
    ins_client = s16[2].astype(np.int32)
    rem_client = s16[3].astype(np.int32)
    d_ins = s16[4].astype(np.int32)
    ins_seq = np.where(d_ins < 0, np.int32(DEV_UNASSIGNED),
                       base_ins[:, None] + d_ins).astype(np.int32)
    d_rem = s16[5].astype(np.int32)
    rem_seq = np.where(
        d_rem == -1, np.int32(DEV_NO_REMOVE),
        np.where(d_rem == -2, np.int32(DEV_UNASSIGNED),
                 base_rem[:, None] + d_rem)).astype(np.int32)
    d_anno = a16.astype(np.int32)
    anno = np.where(d_anno < 0, np.int32(-1),
                    base_anno[:, None, None] + d_anno).astype(np.int32)

    bad = np.nonzero(ok == 0)[0]
    if bad.size:
        # Exact-plane refetch for the overflowing docs only.
        _counters.increment("summarize.wire_refetch", int(bad.size))
        idx_p, _ = pad_pow2_indices(bad)
        planes, anno_x = _exact_rows(packed, jnp.asarray(idx_p), mx)
        planes = np.asarray(planes)
        anno_x = np.asarray(anno_x)
        nbytes += planes.nbytes + anno_x.nbytes
        op32 = np.array(op32)  # the zero-copy device view is read-only
        for k, d in enumerate(bad):
            op32[d], origin_off[d], length[d] = (
                planes[0, k], planes[1, k], planes[2, k])
            ins_seq[d], ins_client[d] = planes[3, k], planes[4, k]
            rem_seq[d], rem_client[d] = planes[5, k], planes[6, k]
            anno[d] = anno_x[k]
    _counters.increment("summarize.bytes_d2h", nbytes)
    return (op32, origin_off, length, anno, ins_seq, ins_client,
            rem_seq, rem_client, counts)


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

@jax.jit
def visible_mask(state: DocState, ref_seq, client):
    vis, _, _ = visibility(state, ref_seq, client)
    return vis


@jax.jit
def doc_length(state: DocState, ref_seq, client):
    _, vlen, _ = visibility(state, ref_seq, client)
    return jnp.sum(vlen)
