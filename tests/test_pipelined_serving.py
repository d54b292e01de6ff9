"""Pipelined serving: the megakernel ring.

A pipelined fast flush stages all its windows and leaves as ONE
serve_megakernel dispatch whose readback the next flush's drain joins
(tpu_sequencer._ring, docs/serving_pipeline.md R10). These tests pin its
safety contract:

- multi-window rings with a host rescue MID-RING must produce sequence
  numbers and lane state identical to ``pipelined=False`` (per-window
  dispatch);
- K windows in one megakernel emit bit-identically to per-window
  dispatch, and risky windows dispatch and drain at once;
- adaptive window sizing only ever draws T from the fixed t_buckets grid,
  and a warm pipeline does not retrace serve_megakernel per flush
  (JitRetraceProbe regression);
- occupancy bookkeeping stays consistent with the device counts the
  narrow result reports.
"""

import json

import numpy as np
import pytest

from fluidframework_tpu.mergetree.client import OP_ANNOTATE, OP_INSERT
from fluidframework_tpu.protocol.messages import (
    Boxcar,
    DocumentMessage,
    MessageType,
)
from fluidframework_tpu.server import pump as pump_mod
from fluidframework_tpu.server.tpu_sequencer import (
    MergeLaneStore,
    TpuSequencerLambda,
)
from fluidframework_tpu.server.log import QueuedMessage
from fluidframework_tpu.server.wire import boxcar_to_wire
from fluidframework_tpu.telemetry import counters

pytestmark = pytest.mark.skipif(not pump_mod.available(),
                                reason="native wirepump unavailable")


class _Ctx:
    def checkpoint(self, *_):
        pass

    def error(self, err, restart=False):
        raise err


def _lam(emit=None, **kw):
    kw.setdefault("client_timeout_s", 0.0)
    return TpuSequencerLambda(_Ctx(), emit=emit or (lambda *a: None),
                              nack=lambda *a: None, **kw)


def _qm(offset, doc, box):
    return QueuedMessage(topic="rawdeltas", partition=0, offset=offset,
                         key=doc, value=boxcar_to_wire(box))


def _join(cid):
    return DocumentMessage(0, -1, MessageType.CLIENT_JOIN,
                           data=json.dumps({"clientId": cid,
                                            "detail": {}}))


def _insert(csn, pos, text):
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=csn - 1,
        type=MessageType.OPERATION,
        contents={"address": "s", "contents": {
            "address": "t", "contents": {
                "type": OP_INSERT, "pos1": pos, "seg": {"text": text}}}})


def _annotate(csn, start, end, props):
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=csn - 1,
        type=MessageType.OPERATION,
        contents={"address": "s", "contents": {
            "address": "t", "contents": {
                "type": OP_ANNOTATE, "pos1": start, "pos2": end,
                "props": props}}})


def _lww_set(csn, key, value):
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=csn - 1,
        type=MessageType.OPERATION,
        contents={"address": "s", "contents": {
            "address": "m", "contents": {
                "type": "set", "key": key, "value": value, "pid": "p"}}})


def _emit_key(doc_id, m):
    return (doc_id, m.sequence_number, m.minimum_sequence_number,
            m.client_id, m.client_sequence_number)


def _drive(lam, waves, emits):
    off = 0
    for wave in waves:
        for doc, box in wave:
            lam.handler_raw(_qm(off, doc, box))
            off += 1
        lam.flush()
    lam.drain()


def _deep_ragged_waves(n_waves=4, docs=3, deep_ops=8, shallow_ops=2,
                       annotates=None):
    """Doc r0 types deep bursts (spans multiple T=4 windows with
    t_buckets=(1, 4)); the rest send keystrokes. Inserts land at pos 0
    so content is order-sensitive: any ring reordering corrupts it.
    `annotates` maps a wave index to a count of annotates doc r0 appends
    over its first two characters (all on one row's ring)."""
    waves = []
    csn = {d: 0 for d in range(docs)}
    for w in range(n_waves):
        wave = []
        for d in range(docs):
            doc = f"r{d}"
            n = deep_ops if d == 0 else shallow_ops
            msgs = [] if w else [_join(f"c{d}")]
            for _ in range(n):
                csn[d] += 1
                msgs.append(_insert(csn[d], 0, f"{csn[d] % 10}"))
            if d == 0:
                for i in range((annotates or {}).get(w, 0)):
                    csn[d] += 1
                    msgs.append(_annotate(csn[d], 0, 2, {f"a{i}": w}))
            wave.append((doc, Boxcar("t", doc, f"c{d}", msgs)))
        waves.append(wave)
    return waves


def _merge_rows(lam, key):
    """The key's live rows (the first `count` of its gathered pages) as
    host arrays — the bit-identity probe."""
    import jax
    row = jax.device_get(lam.merge.pages.row(key))
    n = int(row.count)
    return row._replace(**{f: getattr(row, f)[:n] for f in (
        "length", "ins_seq", "ins_client", "rem_seq")})


class TestFoldMidRingBitIdentity:
    def test_multiwindow_overflow_fold_mid_ring_matches_sync(self):
        """An annotate-ring exhaustion in the LAST window of a
        multi-window megakernel ring rolls the doc back to the pre-ring
        view and host-rescues it with the ops of EVERY window of the
        ring; the result must reconverge to EXACTLY the per-window
        (pipelined=False) run: same sequence numbers, same text, same
        content and props, same device lane planes for the fleet."""
        from fluidframework_tpu.mergetree.host import (
            flatten_snapshot_content)
        waves = _deep_ragged_waves(n_waves=5, deep_ops=8,
                                   annotates={1: 2, 2: 6})

        def run(pipelined):
            emits = []
            lam = _lam(lambda d, m: emits.append(_emit_key(d, m)),
                       merge_store=MergeLaneStore(page_rows=4),
                       t_buckets=(1, 4))
            lam.pipelined = pipelined
            _drive(lam, waves, emits)
            return lam, emits

        rec0 = counters.get("serving.recovery_dispatches")
        sync_lam, sync_emits = run(False)
        ring_lam, ring_emits = run(True)
        # The scenario actually exercised the rescue in both modes.
        assert sync_lam.merge.paged_rescues >= 1
        assert ring_lam.merge.paged_rescues == sync_lam.merge.paged_rescues
        assert counters.get("serving.recovery_dispatches") > rec0
        # The STREAM is bit-identical, order included: an out-of-order
        # drain or a misattached emit_args would reorder across windows
        # while keeping the same multiset.
        assert sync_emits == ring_emits
        snaps_s = sync_lam.summarize_documents()
        snaps_r = ring_lam.summarize_documents()
        for d in range(3):
            key = (f"r{d}", "s", "t")
            assert sync_lam.channel_text(*key) == \
                ring_lam.channel_text(*key)
            assert flatten_snapshot_content(snaps_s[key]) == \
                flatten_snapshot_content(snaps_r[key])
            if d == 0:
                continue  # segmentation is the rescue's own fold
            a = _merge_rows(sync_lam, key)
            b = _merge_rows(ring_lam, key)
            for name in ("length", "ins_seq", "ins_client", "rem_seq",
                         "count", "min_seq", "seq"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, name)),
                    np.asarray(getattr(b, name)),
                    err_msg=f"{key} plane {name} diverged")

    def test_natural_gate_routes_risky_windows_sync(self):
        """Risky windows (non-insert merge traffic) dispatch with the
        windows staged before them and drain at once, so their likely
        rescue runs with nothing behind it — the stream still matches
        the per-window run, and the rings outnumber the flushes."""
        waves = _deep_ragged_waves(n_waves=4, deep_ops=8,
                                   annotates={1: 1, 3: 1})

        def run(pipelined):
            emits = []
            lam = _lam(lambda d, m: emits.append(_emit_key(d, m)),
                       merge_store=MergeLaneStore(page_rows=4),
                       t_buckets=(1, 4))
            lam.pipelined = pipelined
            _drive(lam, waves, emits)
            return lam, emits

        sync_lam, sync_emits = run(False)
        rings0 = counters.get("serving.megakernel_rings")
        ring_lam, ring_emits = run(True)
        assert counters.get("serving.megakernel_rings") - rings0 > \
            len(waves)
        assert sync_emits == ring_emits  # order included
        for d in range(3):
            key = (f"r{d}", "s", "t")
            assert sync_lam.channel_text(*key) == \
                ring_lam.channel_text(*key)


class TestRingDepth:
    def test_ring_runs_deeper_than_one(self):
        """Clean multi-window flushes must actually pipeline: each leaves
        as one megakernel carrying several windows, so occupancy climbs
        past one in-flight window, and every deferred window drains."""
        counters.gauge("serving.ring_peak_occupancy", 0.0)
        waves = _deep_ragged_waves(n_waves=6, deep_ops=8, shallow_ops=2)
        emits = []
        lam = _lam(lambda d, m: emits.append(_emit_key(d, m)),
                   t_buckets=(1, 4))
        lam.pipelined = True
        _drive(lam, waves, emits)
        assert counters.get("serving.ring_peak_occupancy") > 1
        assert not lam._ring
        # Every wave's messages were emitted exactly once.
        assert len(emits) == len({e for e in emits})
        assert len(emits) == sum(
            len(box.contents) for wave in waves for _, box in wave)

    def test_drain_is_idempotent_and_settles(self):
        lam = _lam()
        lam.pipelined = True
        lam.handler_raw(_qm(0, "d0", Boxcar("t", "d0", "c0", [
            _join("c0"), _insert(1, 0, "a")])))
        lam.flush()
        lam.drain()
        lam.drain()
        assert lam.channel_text("d0", "s", "t") == "a"


class TestAdaptiveWindowSizing:
    def test_adaptive_t_draws_from_bounded_shape_set(self):
        """Whatever the backlog distribution or histogram state, T comes
        from the fixed t_buckets grid and depth never exceeds the
        configured ring depth."""
        lam = _lam()
        lam.pipelined = True
        rng = np.random.default_rng(7)
        seen = set()
        for _ in range(200):
            n_docs = int(rng.integers(1, 64))
            depths = rng.integers(1, 400, size=n_docs)
            t, depth = lam._adaptive_shape(int(depths.max()),
                                           depths.astype(np.int64))
            seen.add(t)
            assert t in lam.t_buckets
            assert 1 <= depth <= lam.ring_depth
        # The policy actually adapts: more than one bucket chosen.
        assert len(seen) > 1

    def test_ragged_backlog_narrows_t_uniform_keeps_depth(self):
        lam = _lam()
        lam.pipelined = True
        # Uniform: every doc 16 deep -> exact-depth single window.
        t_uniform, _ = lam._adaptive_shape(
            16, np.full(64, 16, np.int64))
        assert t_uniform == 16
        # Ragged: one storm doc atop a keystroke fleet -> T follows the
        # p95 depth, the storm doc spans extra windows.
        depths = np.full(64, 2, np.int64)
        depths[0] = 256
        t_ragged, _ = lam._adaptive_shape(256, depths)
        assert t_ragged < 256
        assert t_ragged in lam.t_buckets

    def test_warm_pipeline_does_not_retrace_serve_window(self):
        """JitRetraceProbe-style regression: after warm-up, further
        flushes with the same traffic shape must not grow
        serve_megakernel's compile cache (adaptive sizing stays on the
        warmed grid)."""
        from fluidframework_tpu.server import serve_step
        waves = _deep_ragged_waves(n_waves=8, deep_ops=2, shallow_ops=2)
        lam = _lam()
        lam.pipelined = True
        _drive(lam, waves[:5], [])

        def cache_size():
            try:
                return serve_step.serve_megakernel._cache_size()
            except TypeError:
                return serve_step.serve_megakernel._cache_size
        warm = cache_size()
        _drive(lam, waves[5:], [])
        assert cache_size() == warm, \
            "serve_megakernel retraced on a warm traffic shape"


class TestOccupancyHints:
    def test_hints_track_device_counts_after_drain(self):
        """The narrow result keeps the host's occupancy exact: after a
        full drain, every LWW lane's count_hint matches the device's
        occupied key slots with nothing left pending, and every merge
        doc's host count mirror matches the rows its pages hold (the
        exact int32 scalar plane)."""
        lam = _lam()
        lam.pipelined = True
        waves = _deep_ragged_waves(n_waves=3, deep_ops=3, shallow_ops=3)
        csn = 100
        for w, wave in enumerate(waves):
            msgs = [] if w else [_join("cm")]
            for i in range(5):
                csn += 1
                msgs.append(_lww_set(csn, f"k{w}.{i}", i))
            wave.append(("m0", Boxcar("t", "m0", "cm", msgs)))
        _drive(lam, waves, [])
        checked = 0
        for bucket in lam.lww.buckets:
            live = [i for i, k in enumerate(bucket.used) if k is not None]
            if not live:
                continue
            slots = np.count_nonzero(np.asarray(bucket.state.key) >= 0,
                                     axis=-1)
            np.testing.assert_array_equal(bucket.count_hint[live],
                                          slots[live])
            assert not bucket.hint_pending[live].any()
            checked += len(live)
        assert checked >= 1
        pg = lam.merge.pages
        for d in range(3):
            key = (f"r{d}", "s", "t")
            row = np.asarray(pg.row(key).length)
            assert pg.counts[key] == int(np.count_nonzero(row)), key

    def test_donated_windows_counted(self):
        counters.reset()
        lam = _lam()
        lam.pipelined = True
        waves = _deep_ragged_waves(n_waves=3, deep_ops=2, shallow_ops=2)
        _drive(lam, waves, [])
        assert counters.get("serving.ring_donated_windows") > 0

    def test_mesh_placement_disables_lane_state_donation(self):
        """jax 0.4.37: a donated dp-sharded executable returns corrupt
        lane planes when reloaded warm from the persistent compilation
        cache (cold compiles are correct) — mesh-placed pools must stay
        on serve_megakernel_keep until a jax upgrade clears the repro
        (docs/serving_pipeline.md R6)."""
        from fluidframework_tpu.parallel.mesh import make_mesh
        from fluidframework_tpu.server import serve_step
        assert _lam().merge.pages.donate is True
        mesh_lam = _lam(mesh=make_mesh(sp=1))
        assert mesh_lam.merge.pages.donate is False
        calls = []
        real = serve_step.serve_megakernel_keep

        def spy(*a):
            calls.append(1)
            return real(*a)

        serve_step.serve_megakernel_keep = spy
        try:
            mesh_lam.handler_raw(_qm(0, "d0", Boxcar("t", "d0", "c0", [
                _join("c0"), _insert(1, 0, "a")])))
            mesh_lam.flush()
            mesh_lam.drain()
        finally:
            serve_step.serve_megakernel_keep = real
        assert calls, "mesh ring did not take the non-donating program"
        assert mesh_lam.channel_text("d0", "s", "t") == "a"


def _keystroke_waves(n_waves=10, docs=4, ops=3, bad_flush=None,
                     bad_pos=None):
    """Per-doc keystroke waves; with t_buckets=(1, 4) and ops > 4 a
    flush spans several windows, which one megakernel ring carries.
    `bad_flush` injects one insert at an impossible position (`bad_pos`
    beyond the doc's length) on an extra channel-owning doc —
    structurally unpredictable overflow no fit proof can see."""
    waves = []
    csn = {d: 0 for d in range(docs)}
    bad_csn = 0
    for w in range(n_waves):
        wave = []
        for d in range(docs):
            doc = f"k{d}"
            msgs = [] if w else [_join(f"c{d}")]
            for _ in range(ops):
                csn[d] += 1
                msgs.append(_insert(csn[d], 0, f"{csn[d] % 10}"))
            wave.append((doc, Boxcar("t", doc, f"c{d}", msgs)))
        if bad_flush is not None:
            msgs = [] if w else [_join("cbad")]
            bad_csn += 1
            pos = bad_pos if w == bad_flush else 0
            msgs.append(_insert(bad_csn, pos, "X"))
            wave.append(("kbad", Boxcar("t", "kbad", "cbad", msgs)))
        waves.append(wave)
    return waves


class TestFusedBursts:
    """K windows in ONE serve_megakernel ring (docs/serving_pipeline.md
    R10) are bit-identical to per-window dispatch — emit order, lane
    planes, and recovery semantics included."""

    def _run(self, waves, pipelined, stall=None, **lam_kw):
        emits = []
        lam_kw.setdefault("t_buckets", (1, 4))
        lam = _lam(lambda d, m: emits.append(_emit_key(d, m)), **lam_kw)
        lam.pipelined = pipelined
        if stall is not None:
            lam.stall_hook = stall
        _drive(lam, waves, emits)
        return lam, emits

    def test_burst_bit_identical_to_sync_and_ring(self):
        """Clean multi-window keystroke traffic: megakernel rings must
        reproduce the per-window (pipelined=False) run EXACTLY — stream
        order, text, and the device lane planes — and actually carry
        more than one window per dispatch."""
        waves = _keystroke_waves(n_waves=10, ops=8)
        sync_lam, sync_emits = self._run(waves, pipelined=False)
        counters.reset()
        ring_lam, ring_emits = self._run(waves, pipelined=True)
        assert counters.get("serving.bursts") > 0
        assert counters.get("serving.burst_windows") > \
            counters.get("serving.bursts")
        assert sync_emits == ring_emits  # order included
        for d in range(4):
            key = (f"k{d}", "s", "t")
            assert sync_lam.channel_text(*key) == \
                ring_lam.channel_text(*key)
            assert sync_lam.merge.pages.counts[key] == \
                ring_lam.merge.pages.counts[key]
            a = _merge_rows(sync_lam, key)
            b = _merge_rows(ring_lam, key)
            for name in ("length", "ins_seq", "ins_client", "rem_seq",
                         "count", "min_seq", "seq"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, name)),
                    np.asarray(getattr(b, name)),
                    err_msg=f"{key} plane {name} diverged")

    def test_mid_burst_overflow_quarantine(self):
        """An insert at an impossible position (beyond the doc's visible
        length) flags overflow on a window no fit proof can see —
        mid-ring, with sibling windows in the SAME megakernel. The flag
        is sticky, so the ring's last window rolls the doc back to its
        pre-ring view and rescues it with the whole ring's ops, exactly
        as the per-window run rescues its own window: the emitted stream
        and the doc's fate (rescued or opaque) match, and the healthy
        fleet is untouched."""
        waves = _keystroke_waves(n_waves=8, ops=8, bad_flush=3,
                                 bad_pos=500)
        sync_lam, sync_emits = self._run(waves, pipelined=False)
        counters.reset()
        ring_lam, ring_emits = self._run(waves, pipelined=True)
        assert counters.get("serving.bursts") > 0
        assert counters.get("serving.recovery_dispatches") > 0
        assert ring_lam.merge.paged_rescues == sync_lam.merge.paged_rescues
        assert ring_lam.merge.paged_rescues >= 1
        assert ring_lam.merge.overflow_drops == \
            sync_lam.merge.overflow_drops
        assert sync_emits == ring_emits  # order included
        for d in range(4):
            key = (f"k{d}", "s", "t")
            assert sync_lam.channel_text(*key) == \
                ring_lam.channel_text(*key)
        bad = ("kbad", "s", "t")
        assert (bad in ring_lam.merge.opaque) == \
            (bad in sync_lam.merge.opaque)
        assert ring_lam.channel_text(*bad) == sync_lam.channel_text(*bad)

    def test_defer_risky_windows_forces_burst_breakup(self):
        """A risky window landing mid-accumulation BREAKS the ring: the
        windows staged before it dispatch with it and drain at once, the
        windows after it start a new ring — more rings than flushes —
        and the stream still matches the per-window run."""
        waves = _deep_ragged_waves(n_waves=8, deep_ops=8,
                                   annotates={2: 1, 5: 1})
        _, sync_emits = self._run(waves, pipelined=False)
        counters.reset()
        _, ring_emits = self._run(waves, pipelined=True)
        assert counters.get("serving.megakernel_rings") > len(waves)
        assert counters.get("serving.burst_windows") > \
            counters.get("serving.megakernel_rings")
        assert sync_emits == ring_emits  # order included

    def test_faultplan_stall_during_burst_is_deterministic(self):
        """A FaultPlan device stall firing while rings accumulate must
        reproduce bit-identically from its seed: same fault trace
        fingerprint, same emitted stream, run twice."""
        from fluidframework_tpu.testing import faultinject

        def once():
            plan = faultinject.FaultPlan(seed=1234, stall=1.0,
                                         stall_range_ms=(0.1, 0.4))
            waves = _keystroke_waves(n_waves=8, ops=8)
            counters.reset()
            _, emits = self._run(
                waves, pipelined=True,
                stall=lambda: faultinject.stall(plan))
            return emits, plan.fingerprint(), \
                counters.get("serving.bursts")

        emits_a, fp_a, bursts_a = once()
        emits_b, fp_b, bursts_b = once()
        assert bursts_a > 0 and bursts_b > 0
        assert fp_a == fp_b
        assert emits_a == emits_b

    def test_burst_lowering_failure_falls_back_per_window(
            self, monkeypatch):
        """A multi-window ring whose pallas phases fail to lower retries
        the SAME ring with the scan op-phase (counted, sticky): no
        window is lost or re-ordered, and the stream equals the
        per-window run."""
        from fluidframework_tpu.server import serve_step
        waves = _keystroke_waves(n_waves=8, ops=8)
        _, sync_emits = self._run(waves, pipelined=False)
        real = serve_step.serve_megakernel
        ks = []

        def refuse_pallas(tstate, pool, lww, tx, *rest):
            if rest[-2]:  # fused modes; the scan retry passes False
                raise RuntimeError("ring lowering refused")
            ks.append(int(tx.shape[0]))
            return real(tstate, pool, lww, tx, *rest)

        monkeypatch.setattr(serve_step, "serve_megakernel", refuse_pallas)
        counters.reset()
        emits = []
        lam = _lam(lambda d, m: emits.append(_emit_key(d, m)),
                   t_buckets=(1, 4))
        lam.pipelined = True
        lam.megakernel_interpret = True
        _drive(lam, waves, emits)
        assert counters.get("serving.megakernel_fallbacks") == 1
        assert max(ks) >= 2  # the retried rings carried several windows
        assert sync_emits == emits  # order included

    def test_occupancy_hints_count_staged_burst_windows(self):
        """A K-window ring must read as ring-fill K, not 1 — the
        admission controller's fill term would otherwise see a long scan
        step as a calm, mostly-empty ring."""
        lam = _lam(t_buckets=(1, 4))
        lam.pipelined = True
        waves = _keystroke_waves(n_waves=6, ops=15)
        off = 0
        fills = []
        for wave in waves:
            for doc, box in wave:
                lam.handler_raw(_qm(off, doc, box))
                off += 1
            lam.flush()
            fills.append(lam.occupancy_hints()["ring_occupancy"])
        # Each flush's 15 ops per doc (16 with the first flush's join)
        # span four T=4 windows, all in flight in the one ring the next
        # flush joins.
        assert fills == [4] * len(waves), fills
        lam.drain()
        assert lam.occupancy_hints()["ring_occupancy"] == 0
        assert not lam._staged and not lam._ring


class TestMegakernelRing:
    """R10 (docs/serving_pipeline.md): the paged fast flush stages every
    window into page-group jobs and leaves as ONE serve_megakernel ring
    per flush. These tests pin the ring's fallback contract and its
    jit-signature discipline."""

    def _run_paged(self, waves, interpret=False, **lam_kw):
        emits = []
        lam = _lam(lambda d, m: emits.append(_emit_key(d, m)), **lam_kw)
        lam.pipelined = True
        lam.megakernel_interpret = interpret
        _drive(lam, waves, emits)
        return lam, emits

    def test_megakernel_lowering_failure_degrades_sticky_and_counted(
            self, monkeypatch):
        """A pallas lowering failure mid-dispatch must degrade INSIDE
        the same ring (retry with the scan op-phase — still one
        dispatch), count serving.megakernel_fallbacks, pin the degrade
        sticky so later rings skip the doomed mode, and leave the
        stream identical to the per-window (pipelined=False) run."""
        from fluidframework_tpu.server import serve_step
        waves = _keystroke_waves(n_waves=8)
        b_emits = []
        sync = _lam(lambda d, m: b_emits.append(_emit_key(d, m)))
        _drive(sync, waves, b_emits)

        real = serve_step.serve_megakernel

        def refuse_pallas(tstate, pool, lww, tx, *rest):
            fused = rest[-2]
            if fused:  # the pallas op-phase modes; scan retry passes False
                raise RuntimeError("pallas lowering refused")
            return real(tstate, pool, lww, tx, *rest)

        monkeypatch.setattr(serve_step, "serve_megakernel",
                            refuse_pallas)
        counters.reset()
        _, emits = self._run_paged(waves, interpret=True)
        assert counters.get("serving.megakernel_rings") >= 2
        # Sticky: exactly the first ring attempted pallas and fell back.
        assert counters.get("serving.megakernel_fallbacks") == 1
        assert emits == b_emits  # order included

    def test_megakernel_k_grid_pins_jit_signatures(self):
        """The ring length K is quantized to the burst grid so the
        megakernel's jit cache CANNOT fragment on scan length — and a
        repeat of the same workload must add zero compiles."""
        from fluidframework_tpu.server import serve_step
        from fluidframework_tpu.telemetry.compile_ledger import ledger

        waves = _deep_ragged_waves(n_waves=8, deep_ops=8)
        real = serve_step.serve_megakernel
        ks = []

        def record(tstate, pool, lww, tx, *rest):
            ks.append(int(tx.shape[0]))
            return real(tstate, pool, lww, tx, *rest)

        serve_step.serve_megakernel = record
        try:
            counters.reset()
            lam, _ = self._run_paged(waves, t_buckets=(1, 4))
            grid = set(lam._burst_k_grid) | {1}
            assert ks and set(ks) <= grid
            # Amortization: rings carried more windows than dispatches.
            assert counters.get("serving.megakernel_windows") > \
                counters.get("serving.megakernel_rings")

            def mega_compiles():
                sym = ledger.snapshot().get("symbols", {})
                return sum(v.get("compiles", 0)
                           for k, v in sym.items()
                           if k.startswith("serve.megakernel"))

            warm = mega_compiles()
            self._run_paged(waves, t_buckets=(1, 4))
            assert mega_compiles() == warm
        finally:
            serve_step.serve_megakernel = real
