"""The fast serving flush's device program (tpu_sequencer._flush_raw).

ONE jitted program per ring of K fast windows — the serving megakernel
(``serve_megakernel``, docs/serving_pipeline.md R10): gather each page
group's documents from the page pool once, then scan the K windows, each
doing [B, T] deli ticketing for the whole partition, then per page group
and LWW bucket the merge/LWW apply — each op's assigned seq/msn gathered
from the ticket output by (doc lane, step), the admitted-ops-only
discipline of pipeline.full_step generalized to channel lanes that live in
a different lane space than documents — and finally everything the host
needs packed into ONE int16 vector per window (per-op seq/msn, nack flags,
per-doc next_seq, overflow summary bits, exact post scalars); then
scatter the group views back. Every dispatch and every fetch pays a fixed
host cost, so a ring is exactly one dispatch and one D2H.

``serve_paged_burst`` is the object path's K-chunk apply over the same
pool (tpu_sequencer.MergeLaneStore._apply_paged).

Reference analog: deli/lambda.ts:142 ticket() feeding downstream lambdas;
the merge/LWW applies play Scribe's materialization role fused into the
same device program.

Observability: the WHOLE program is one dispatch by design, so host-side
tracing (telemetry/tracing.py) cannot subdivide it — the serving flush's
named sub-spans bracket it instead: ``serving.pack`` (staging the cols
this function consumes), ``serving.dispatch`` (this jit call),
``serving.readback`` (the flat16 D2H), with settle/rescue and payload GC
as their own host stages. Each feeds a ``serving.*`` histogram on
``/metrics.prom`` (docs/observability.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..mergetree import kernel
from ..mergetree.oppack import OpKind, PackedOps
from . import lww_kernel as lk
from . import ticket_kernel as tk


def _serve_window_impl(tstate, ticket_cols, merge_states, merge_cols,
                       lww_states, lww_cols, fused=False, merge_runs=None,
                       stats=False):
    """One fast window: the traced scan body of ``serve_megakernel``.

    The merge states are gathered page-group views whose post scalars
    the host must adopt (paged scalars are host-authoritative between
    flushes), so each merge apply also produces the narrow tuple
    (overflow int16, count, min_seq, seq) — extracted IN-KERNEL by the
    fused pallas program on its last op step, or derived identically by
    the scan fallback — and flat16 carries a per-group int32-halves
    scalar tail (the ``paged_tail`` packing below). ``fused ==
    "interpret"`` runs the SAME pallas program through the pallas
    interpreter so CPU tier-1 exercises the identical kernel; any other
    truthy ``fused`` means Mosaic-lowered.

    Ring windows pad to the union of staged groups/buckets, so a group a
    window never staged carries an all-NOOP op plane: each apply is
    lax.cond-guarded on "any real op", skipping the whole T-step apply
    for padding (a NOOP stream is an exact identity on the lane state
    either way, so results are bit-identical — the guard only saves the
    padded window's compute).

    ticket_cols: [4, B, T] int32 (kind, client, cseq, refseq).
    merge_cols:  per group [12, lanes, Tm] (10 PackedOps columns +
                 doc_idx + t_idx).
    merge_runs:  per group [4, lanes, Tm, RUN_K] INSERT_RUN members, or
                 None.
    lww_cols:    per bucket [6, lanes, Tm] (kind, key, val, delta,
                 doc_idx, t_idx).
    Returns (tstate', merge_states', lww_states', flat16, msn32) where
    flat16 is the NARROW int16 result the host fetches every window:
    [seq_delta B*T | msn_delta B*T | flags B*T | next_seq as (lo B, hi B)
    | msn_base as (lo B, hi B) | msn_ok bit | overflow-any bits |
    per-lane overflow planes (merge then LWW, lanes each) | per-lane
    occupancy planes (same order) | per-group post (count, min_seq,
    seq) as (lo, hi) halves | (stats=True only) the device telemetry
    plane: device_stats.N_SERVE int32 slots as (lo, hi) int16 halves],
    decoded by tpu_sequencer._finish_window; msn32 is the exact int32
    msn plane, fetched ONLY when the window's msn span overflows the
    delta (msn_ok == 0; one global bit for the whole window).

    ``stats`` (static) appends the device-resident telemetry plane
    (telemetry/device_stats.py SERVE_SLOTS): admitted ops by kind,
    ticket admissions/nacks, overflow-lane and noop-skip counts, and
    post-window lane fill — counted INSIDE the program from the same
    masks the applies use, so a K-window ring reports exact per-window
    facts with zero extra dispatches and zero extra host round-trips
    (the plane rides this same flat16). Pure output: the op phases
    never read it, so results are bit-identical with it on or off."""
    raw = tk.RawOps(client=ticket_cols[1], client_seq=ticket_cols[2],
                    ref_seq=ticket_cols[3], kind=ticket_cols[0])
    tstate, ticketed = tk._scan_tickets(tstate, raw, batched=True,
                                        require_join=True)
    seq_bt, msn_bt = ticketed.seq, ticketed.min_seq

    if merge_runs is None:
        merge_runs = [None] * len(merge_cols)
    # Device telemetry accumulators (stats=True): counted from the SAME
    # ok-masks the applies consume, so the host mirror derived from the
    # decoded ticket results reconciles exactly.
    zero = jnp.zeros((), jnp.int32)
    st_kind = [zero] * 6  # INSERT..INSERT_RUN admitted counts
    st_lww = zero
    st_skips = zero
    new_merge = []
    merge_narrow = []  # per-group narrow tuples

    def _narrow(s):
        # fluidlint: disable=DTYPE_DRIFT — deliberate 16-bit wire packing
        # (the overflow plane rides flat16).
        return (s.overflow.astype(jnp.int16), s.count, s.min_seq, s.seq)

    from ..mergetree.pallas_apply import (FUSED_MAX_CAPACITY,
                                         apply_ops_fused_pallas)
    interp = fused == "interpret"
    # fluidlint: disable=RETRACE_HAZARD — deliberate bounded unroll: one
    # iteration per page group of the ring, fused so the whole ring stays
    # a single device program.
    for mstate, mc, mr in zip(merge_states, merge_cols, merge_runs):
        packed = PackedOps(kind=mc[0], seq=mc[1], ref_seq=mc[2],
                           client=mc[3], pos1=mc[4], pos2=mc[5],
                           op_id=mc[6], new_len=mc[7], local_seq=mc[8],
                           msn=mc[9])
        seq_g = seq_bt[mc[10], mc[11]]
        msn_g = msn_bt[mc[10], mc[11]]
        ok = (packed.kind != OpKind.NOOP) & (seq_g > 0)
        runs = None
        over_extra = None
        if mr is not None:
            # INSERT_RUN slots: every member gathers ITS OWN ticketed
            # seq; a member the ticket pass nacked (dup/stale — the host
            # packed on a prediction) voids the WHOLE slot and flags the
            # lane, which then takes the standard overflow rollback +
            # scalar re-run. mr: [4, lanes, Tm, K] = len, op_id,
            # doc_lane, t_idx per member (len 0 = padding).
            from ..mergetree.oppack import RunCols
            sub_len, sub_oid = mr[0], mr[1]
            sub_seq = seq_bt[mr[2], mr[3]]
            expected = sub_len > 0
            is_run = packed.kind == OpKind.INSERT_RUN
            mispredict = is_run & jnp.any(expected & (sub_seq <= 0),
                                          axis=-1)
            ok = ok & ~mispredict
            runs = RunCols(length=sub_len,
                           seq=jnp.where(expected, sub_seq, 0),
                           op_id=sub_oid)
            over_extra = jnp.any(mispredict, axis=-1)
        ops2 = packed._replace(
            kind=jnp.where(ok, packed.kind, OpKind.NOOP),
            seq=jnp.where(ok, seq_g, 0),
            msn=jnp.where(ok, msn_g, 0))
        if stats:
            for ki, kv in enumerate((OpKind.INSERT, OpKind.REMOVE,
                                     OpKind.ANNOTATE, OpKind.ACK_INSERT,
                                     OpKind.ACK_REMOVE,
                                     OpKind.INSERT_RUN)):
                st_kind[ki] = st_kind[ki] + jnp.sum(
                    (ops2.kind == kv).astype(jnp.int32))
        if fused and mstate.capacity <= FUSED_MAX_CAPACITY:
            # VMEM-resident fused apply: the group's view stays on-core
            # across the whole op stream (one read + one write instead of
            # the scan kernel's T-step HBM re-read/re-write), extracting
            # the narrow tuple on its last op step. Bit-identical to the
            # scan kernel (tests/test_pallas_apply).
            def apply_m(s, o=ops2, r=runs):
                return apply_ops_fused_pallas(s, o, interpret=interp,
                                              runs=r, extract=True)
        else:
            def apply_m(s, o=ops2, r=runs):
                out = kernel._scan_ops(s, o, batched=True, runs=r)
                return out, _narrow(out)
        active = jnp.any(ops2.kind != OpKind.NOOP)
        if stats:
            st_skips = st_skips + (~active).astype(jnp.int32)
        # kernel.apply_if_any carries state only; this body also threads
        # the narrow tuple, so the pad-skip cond is inlined with a
        # derived-narrow identity arm.
        out, nr = jax.lax.cond(
            active, apply_m, lambda s: (s, _narrow(s)), mstate)
        if over_extra is not None:
            # A nacked INSERT_RUN member voids the slot host-side: the
            # flag must reach BOTH the carried state and the narrow
            # plane the host actually reads.
            out = out._replace(overflow=out.overflow | over_extra)
            nr = (nr[0] | over_extra.astype(jnp.int16),) + nr[1:]
        new_merge.append(out)
        merge_narrow.append(nr)

    new_lww = []
    # fluidlint: disable=RETRACE_HAZARD — deliberate bounded unroll, one
    # iteration per LWW capacity bucket (same contract as the merge loop).
    for lstate, lc in zip(lww_states, lww_cols):
        seq_g = seq_bt[lc[4], lc[5]]
        ok = (lc[0] != lk.LwwKind.NOOP) & (seq_g > 0)
        ops = lk.LwwOps(kind=jnp.where(ok, lc[0], lk.LwwKind.NOOP),
                        key=lc[1], val=lc[2], delta=lc[3],
                        seq=jnp.where(ok, seq_g, 0))
        if stats:
            st_lww = st_lww + jnp.sum(
                (ops.kind != lk.LwwKind.NOOP).astype(jnp.int32))

        def apply_l(s, o=ops):
            return lk._scan(s, o, batched=True)
        active_l = jnp.any(ops.kind != lk.LwwKind.NOOP)
        if stats:
            st_skips = st_skips + (~active_l).astype(jnp.int32)
        new_lww.append(kernel.apply_if_any(apply_l, lstate, active_l))

    flags = ticketed.nacked.astype(jnp.int32) | \
        (ticketed.not_joined.astype(jnp.int32) << 1)
    bits = [tstate.overflow.any()[None].astype(jnp.int32)]
    bits += [s.overflow.any()[None].astype(jnp.int32) for s in new_merge]
    bits += [s.overflow.any()[None].astype(jnp.int32) for s in new_lww]
    # Per-lane overflow planes ride the SAME narrow result (one int16 per
    # staged lane): overflow recovery learns WHICH lanes flagged without
    # touching the post states at all — required once the lane states
    # are donated. The merge planes come from the narrow tuples
    # (in-kernel extracted under fused; bit-identical derivation under
    # the scan fallback — over_extra already OR'd in).
    planes = [nr[0] for nr in merge_narrow]
    # fluidlint: disable=DTYPE_DRIFT — deliberate 16-bit wire packing:
    # the planes ride flat16, the narrow result plane (docstring).
    planes += [s.overflow.astype(jnp.int16) for s in new_lww]
    # Post-window occupancy planes (row count per merge lane, occupied
    # key slots per LWW lane): the host's LWW fit proof keeps its
    # occupancy hints EXACT from every window's own result instead of
    # decaying pessimistic until a compact-tick refresh — no extra device
    # round-trip. The int16 view of the group counts keeps the flat16
    # layout uniform; a large page group can wrap it, so the host adopts
    # merge counts from the exact int32 paged_tail below instead.
    # fluidlint: disable=DTYPE_DRIFT — deliberate 16-bit wire packing
    # (rides the same flat16 narrow result plane).
    planes += [nr[1].astype(jnp.int16) for nr in merge_narrow]
    # fluidlint: disable=DTYPE_DRIFT — deliberate 16-bit wire packing
    # (rides the same flat16 narrow result plane).
    planes += [(s.key >= 0).sum(-1).astype(jnp.int16) for s in new_lww]

    # NARROW result packing: the window result is the serving path's one
    # D2H: one int16 array, the fewest bytes and transfers.
    #   seq  -> delta from the lane's post-window next_seq: bounded by
    #           ops-per-lane <= T (structural); -1 = not admitted.
    #   msn  -> delta from the lane's min admitted msn; a catch-up jump
    #           can exceed int16 (rare) => one window-global ok bit, host
    #           refetches the int32 plane only then.
    #   int32 lane scalars ride as (lo, hi) int16 halves.
    admitted = seq_bt > 0
    next32 = tstate.next_seq.astype(jnp.int32)
    seq_d = jnp.where(admitted, next32[:, None] - seq_bt, -1)
    big = jnp.int32(1 << 30)
    msn_base = jnp.min(jnp.where(admitted, msn_bt, big), axis=1)
    msn_base = jnp.where(msn_base == big, 0, msn_base)
    msn_d = jnp.where(admitted, msn_bt - msn_base[:, None], 0)
    msn_ok = (jnp.max(msn_d) < 32000).astype(jnp.int32)
    msn_d = jnp.minimum(msn_d, 32000)

    def halves(x32):
        # lo may land negative in int16 (bit 15): host re-masks & 0xFFFF.
        return [(x32 & 0xFFFF).astype(jnp.int16),
                (x32 >> 16).astype(jnp.int16)]

    # Scalar-adoption plane: each page group's post count/min_seq/seq as
    # EXACT int32 (lo, hi) halves — the host's page scalars are
    # authoritative between flushes, so the ring's last window adopts
    # these (the post-ring truth). Rides the same one flat16 readback.
    paged_tail = []
    for nr in merge_narrow:
        paged_tail += halves(nr[1]) + halves(nr[2]) + halves(nr[3])

    stats_tail = []
    if stats:
        # The device telemetry plane (telemetry/device_stats.SERVE_SLOTS
        # order): int32 facts as (lo, hi) int16 halves riding the SAME
        # flat16 readback — no extra output, no extra RPC.
        st_vec = jnp.stack(st_kind + [
            st_lww,
            jnp.sum(admitted.astype(jnp.int32)),
            jnp.sum(ticketed.nacked.astype(jnp.int32)),
            jnp.sum(ticketed.not_joined.astype(jnp.int32)),
            sum((s.overflow.astype(jnp.int32).sum() for s in new_merge),
                zero),
            sum((s.overflow.astype(jnp.int32).sum() for s in new_lww),
                zero),
            st_skips,
            sum((s.count.astype(jnp.int32).sum() for s in new_merge),
                zero),
            sum(((s.key >= 0).astype(jnp.int32).sum() for s in new_lww),
                zero),
        ])
        stats_tail = halves(st_vec)

    flat16 = jnp.concatenate(
        [seq_d.ravel().astype(jnp.int16),
         msn_d.ravel().astype(jnp.int16),
         flags.ravel().astype(jnp.int16)]
        + halves(next32) + halves(msn_base)
        # fluidlint: disable=DTYPE_DRIFT — deliberate 16-bit wire packing:
        # flat16 is the NARROW result plane (docstring); decoded by
        # tpu_sequencer._finish_window.
        + [jnp.concatenate([msn_ok[None]] + bits).astype(jnp.int16)]
        + planes + paged_tail + stats_tail)
    # Fetched ONLY when msn_ok == 0 (second RPC on the rare path).
    return tstate, new_merge, new_lww, flat16, msn_bt


def _serve_paged_burst(pool, page_ids, counts, min_seqs, seqs, ops_xs,
                       stats=False):
    """K op windows over PAGED documents in ONE scanned device program
    (the paged serving burst, docs/paged_memory.md): gather each doc's
    pages once, scan the K stacked [B, T] op planes with the gathered
    view as the carry, scatter back once through the page-table plane
    (immutable for the whole burst, so it carries no per-step scan
    leg). The page pool and page tables are the DONATED operands — the
    pool updates in place across the whole burst and page_ids alias
    straight through to the returned plane, so a bulk catch-up stream
    costs one dispatch regardless of its chunk count: view capacity is
    the GROUP's page bucket, not the fleet-wide storm doc's.

    Returns (pool', page_ids, count, min_seq, seq, overflow, over_k,
    pre_view): over_k is the per-chunk overflow plane [K, B] (any bit
    -> the host rolls the flagged docs back from pre_view and runs the
    host rescue with the FULL stream); pre_view is the gathered
    pre-burst group view that makes that rollback possible under
    donation. ``stats`` (static) appends
    the per-chunk device telemetry plane [K, N_PAGED]
    (kernel.paged_stats_vec riding the scan ys — per-chunk facts from
    the one dispatch the burst already is)."""
    from ..mergetree import kernel

    pre = kernel.gather_pages(pool, page_ids, counts, min_seqs, seqs)

    def body(view, ops):
        out = kernel._scan_ops(view, ops, batched=True)
        if stats:
            return out, (out.overflow, kernel.paged_stats_vec(ops, out))
        return out, out.overflow

    out, ys = jax.lax.scan(body, pre, ops_xs)
    over_k = ys[0] if stats else ys
    pool2 = kernel.scatter_pages(pool, page_ids, out)
    # page_ids pass straight through as an output (identity), which is
    # what lets XLA alias the donated plane; tables are immutable for
    # the whole burst, so they carry no per-step scan leg.
    base = (pool2, page_ids, out.count, out.min_seq, out.seq,
            out.overflow, over_k, pre)
    if stats:
        return base + (ys[1],)
    return base


serve_paged_burst = functools.partial(
    jax.jit, donate_argnums=(0, 1), static_argnums=(6,))(
        _serve_paged_burst)

# Non-donating K-chunk burst for MESH-placed pools (serving_pipeline.md
# R6: donation never reaches a mesh-placed dispatch; MESH_DONATION_GATE
# is the lint half of the same contract).
serve_paged_burst_keep = functools.partial(
    jax.jit, static_argnums=(6,))(_serve_paged_burst)


def _serve_megakernel(tstate, pool, lww_states, ticket_xs, page_ids,
                      counts, min_seqs, seqs, merge_xs, lww_xs, runs_xs,
                      fused=False, stats=False):
    """K fast serving windows over the paged merge lanes in ONE device
    program — the serving megakernel (docs/serving_pipeline.md R10).

    The native pump's fast flush stages its merge rows as PAGE-GROUP
    jobs (one group per pow2 page-count class,
    tpu_sequencer.MergeLaneStore.flush_lane_for), and the whole
    pre-staged ring drains as one dispatch. The program:

      1. gathers each group's documents ONCE by page id
         (kernel.gather_pages — view capacity is the GROUP's page
         bucket, never a fleet-wide padded plane),
      2. scans the K stacked windows with ``_serve_window_impl`` as the
         body (ticketing + op applies + narrow extraction), the gathered
         group views + LWW bucket states + ticket state as the carry —
         under ``fused`` each group×window apply is one pallas kernel
         invocation that applies the op phases VMEM-resident and
         EXTRACTS the narrow planes (overflow int16, count/min_seq/seq
         int32) on its own last op step (``fused == "interpret"`` runs
         the identical program through the pallas interpreter for CPU
         tier-1; ``fused=False`` is the counted scan-path fallback,
         bit-identical by construction),
      3. scatters each group's post view back through its immutable
         page table.

    xs layout:
      ticket_xs: [K, 4, B, T]
      page_ids/counts/min_seqs/seqs: per group, the dispatch-time paged
        staging ([n_pad, p2] int32 tables + [n_pad] scalars; pid -1 =
        padding) — immutable for the whole ring, NOT scanned over.
      merge_xs:  per group [K, 12, n_pad, Tm] (NOOP-padded where a
                 window staged nothing for the group)
      lww_xs:    per LWW union bucket [K, 6, lanes, Tm]
      runs_xs:   per group [K, 4, n_pad, Tm, RUN_K] or None

    Returns (tstate', pool', lww_states', flat16_k [K, flat], msn_k
    [K, B, T], pre_views): flat16 carries the paged scalar tail
    (``_serve_window_impl``) so the host adopts exact post int32
    scalars per window with no extra readback; pre_views are the
    gathered pre-ring group views that make the overflow rollback
    possible under donation (rollback_pages + host rescue, same recovery
    contract as ``_serve_paged_burst``).

    One ring = one dispatch = one readback: dispatches per window
    amortize toward 0 as the ring deepens, and the jit signature depends
    only on (K, group shapes, B, T) — scan length does not fragment the
    grid beyond the K axis, which the sequencer quantizes on its fixed
    grid (``_burst_k_grid``)."""
    pre = tuple(kernel.gather_pages(pool, p, c, m, s)
                for p, c, m, s in zip(page_ids, counts, min_seqs, seqs))

    def body(carry, xs):
        ts, ms, ls = carry
        tc, mc, lc, rc = xs
        ts2, nm, nl, flat16, msn32 = _serve_window_impl(
            ts, tc, list(ms), list(mc), list(ls), list(lc), fused,
            list(rc), stats=stats)
        return (ts2, tuple(nm), tuple(nl)), (flat16, msn32)

    carry, ys = jax.lax.scan(
        body, (tstate, pre, tuple(lww_states)),
        (ticket_xs, tuple(merge_xs), tuple(lww_xs), tuple(runs_xs)))
    ts, ms, ls = carry
    pool2 = pool
    for p, out in zip(page_ids, ms):
        pool2 = kernel.scatter_pages(pool2, p, out)
    return ts, pool2, list(ls), ys[0], ys[1], pre


serve_megakernel = functools.partial(
    jax.jit, donate_argnums=(0, 1, 2), static_argnums=(11, 12))(
        _serve_megakernel)

# Non-donating twin for MESH-placed pools (serving_pipeline.md R6, same
# contract as serve_paged_burst_keep: donation never reaches a
# mesh-placed dispatch).
serve_megakernel_keep = functools.partial(
    jax.jit, static_argnums=(11, 12))(_serve_megakernel)
