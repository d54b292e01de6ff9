"""Paged lane memory (docs/paged_memory.md): the page allocator's
free-list/refcount invariants, gather/scatter-by-page-id conformance
against the scalar oracle, page-granular compaction, the annotate-ring
rescue, and run-twice determinism of paged serving under a seeded
FaultPlan.

The conformance bar mirrors tests/test_kernel.py: on a storm-doc ragged
fleet (one deep document atop many keystroke documents), every channel's
text and the flattened content of every assembled snapshot must equal
the oracle's."""

import json
import random

import numpy as np
import pytest

from test_kernel import GOD, apply_to_oracle, random_schedule

from fluidframework_tpu.mergetree import MergeTreeOracle
from fluidframework_tpu.mergetree.constants import PAGE_ROWS
from fluidframework_tpu.mergetree.host import (GOD_CLIENT,
                                               flatten_snapshot_content)
from fluidframework_tpu.mergetree.paging import (
    BLANK_PAGE,
    PageAllocator,
    PagedMergeStore,
    pages_for,
    pow2_pages,
)
from fluidframework_tpu.protocol.messages import (
    Boxcar,
    DocumentMessage,
    MessageType,
)
from fluidframework_tpu.server.log import QueuedMessage
from fluidframework_tpu.server.tpu_sequencer import (
    MergeLaneStore,
    TpuSequencerLambda,
)
from fluidframework_tpu.server.wire import boxcar_to_wire
from fluidframework_tpu.telemetry import counters


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

class TestPageAllocator:
    def test_alloc_free_and_reuse(self):
        a = PageAllocator(8)
        pids = [a.alloc() for _ in range(4)]
        assert len(set(pids)) == 4 and BLANK_PAGE not in pids
        assert a.pages_in_use == 4
        freed = pids[1]
        assert a.release(freed) is True
        assert a.pages_in_use == 3
        # The freed page is reusable (free list hands it back first).
        assert a.alloc() == freed

    def test_double_free_raises(self):
        a = PageAllocator(4)
        pid = a.alloc()
        assert a.release(pid)
        with pytest.raises(ValueError, match="double free"):
            a.release(pid)

    def test_blank_and_out_of_range_ids_refuse(self):
        a = PageAllocator(4)
        with pytest.raises(ValueError):
            a.release(BLANK_PAGE)
        with pytest.raises(ValueError):
            a.release(99)
        with pytest.raises(ValueError):
            a.retain(0)

    def test_refcounted_share_frees_on_last_release(self):
        a = PageAllocator(4)
        pid = a.alloc()
        a.retain(pid)
        assert a.release(pid) is False  # still one owner
        assert a.pages_in_use == 1
        assert a.release(pid) is True
        with pytest.raises(ValueError, match="double free"):
            a.release(pid)

    def test_grow_extends_free_list(self):
        a = PageAllocator(4)
        got = {a.alloc() for _ in range(3)}  # pool (minus blank) full
        with pytest.raises(IndexError):
            a.alloc()
        a.grow(8)
        more = {a.alloc() for _ in range(4)}
        assert not (got & more)
        assert a.pages_in_use == 7


# ---------------------------------------------------------------------------
# page-table storage
# ---------------------------------------------------------------------------

class TestPagedStore:
    def test_growth_appends_pages_without_moving(self):
        pg = PagedMergeStore(page_rows=8, pages=8)
        key = ("d", "s", "t")
        pg.ensure_rows(key, 5)
        first = list(pg.tables[key])
        pg.ensure_rows(key, 30)  # 4 pages
        assert pg.tables[key][:len(first)] == first  # prefix stable
        assert len(pg.tables[key]) == pages_for(30, 8) == 4

    def test_pool_doubles_when_exhausted(self):
        pg = PagedMergeStore(page_rows=8, pages=4)
        pg.ensure_rows(("k",), 8 * 10)
        assert pg.allocator.capacity >= 16
        assert pg.pool_grows >= 1

    def test_release_trailing_frees_and_zeroes(self):
        pg = PagedMergeStore(page_rows=8, pages=8)
        key = ("d", "s", "t")
        pg.ensure_rows(key, 32)
        dead = pg.tables[key][1:]
        pg.counts[key] = 3  # one page's worth live
        pg.release_trailing(key)
        assert len(pg.tables[key]) == 1
        assert pg.allocator.pages_in_use == 1
        # Freed pages are blank: a fresh alloc hands out canonical rows.
        pool_len = np.asarray(pg.pool.length)
        for pid in dead:
            assert (pool_len[pid] == 0).all()

    def test_pow2_pages_bounds_view_shapes(self):
        assert [pow2_pages(n) for n in (1, 2, 3, 5, 9)] == [1, 2, 4, 8, 16]


def _stream(builder, schedule):
    """test_kernel op tuples -> HostOps via the store's shared builder."""
    out = []
    for op in schedule:
        kind = op[0]
        if kind == "insert":
            _, pos, text, ref_seq, client, seq = op
            out.append(builder.insert_text(pos, text, ref_seq, client, seq))
        elif kind == "remove":
            _, start, end, ref_seq, client, seq = op
            out.append(builder.remove(start, end, ref_seq, client, seq))
        else:
            _, start, end, props, ref_seq, client, seq = op
            out.append(builder.annotate(start, end, props, ref_seq,
                                        client, seq))
    return out


def _ragged_fleet(seed, storm_ops=120, fleet=24, fleet_ops=4):
    """One storm doc + a fleet of keystroke docs, per-doc sequenced
    schedules (the worst case for any layout that pads every lane
    toward the storm doc's depth)."""
    rng = random.Random(seed)
    docs = {("doc", "s", "storm"): random_schedule(rng, 3, storm_ops)}
    for i in range(fleet):
        docs[("doc", "s", f"k{i}")] = random_schedule(rng, 2, fleet_ops)
    return docs


def _oracle_content(schedule):
    """(oracle, flattened visible content) after a test_kernel op
    schedule — the engine-independent reference the paged store's
    assembled snapshots are held to (flatten_snapshot_content)."""
    oracle = MergeTreeOracle(local_client=GOD)
    apply_to_oracle(oracle, schedule)
    return oracle, flatten_snapshot_content(
        {"chunks": [oracle.snapshot_segments()]})


class TestPagedConformance:
    @pytest.mark.parametrize("seed", [1, 7, 23])
    def test_storm_fleet_matches_oracle_and_bucketed(self, seed):
        """Every lane of the ragged fleet — text and the flattened
        (char, props) content of its assembled snapshot — equals the
        oracle, with no capacity ceremony: no overflow drops."""
        schedules = _ragged_fleet(seed)
        paged = MergeLaneStore(page_rows=16)
        paged.apply({k: _stream(paged.builder, s)
                     for k, s in schedules.items()})

        snaps = paged.extract_all()
        for key, schedule in schedules.items():
            oracle, content = _oracle_content(schedule)
            top_seq = max(op[-1] for op in schedule)
            assert paged.text(key) == oracle.get_text(ref_seq=top_seq,
                                                      client=GOD)
            assert flatten_snapshot_content(snaps[key]) == content, key
            # Pages track live rows: no padding past the last page.
            assert len(paged.pages.tables[key]) == pages_for(
                paged.pages.counts[key], 16)
        assert paged.overflow_drops == 0
        assert not paged.opaque

    def test_chunked_stream_rides_one_scanned_burst(self, monkeypatch):
        """A stream longer than the widest T bucket applies through ONE
        serve_step.serve_paged_burst dispatch (stacked [K, B, T] chunks,
        one scan) and must match the oracle exactly."""
        from fluidframework_tpu.server import serve_step
        paged = MergeLaneStore()
        key = ("doc", "s", "bulk")
        n = 700  # > max_t=256 -> K=3 chunks, padded to 4
        calls = []
        real = serve_step.serve_paged_burst

        def spy(*args):
            calls.append(int(args[5].kind.shape[0]))
            return real(*args)

        monkeypatch.setattr(serve_step, "serve_paged_burst", spy)
        b = paged.builder
        schedule = [("insert", 0, "seed ", 0, GOD_CLIENT, 1)]
        for i in range(n):
            schedule.append(("insert", min(i, 3), "ab", i + 1,
                             GOD_CLIENT, i + 2))
        paged.apply({key: _stream(b, schedule)})
        assert calls == [4]
        oracle, _ = _oracle_content(schedule)
        assert paged.text(key) == oracle.get_text()
        assert len(paged.text(key)) == 5 + 2 * n

    def test_annotate_ring_exhaustion_takes_host_rescue(self):
        """>anno_slots annotates on one segment in a single window
        exhaust the per-row ring — the one overflow class pages cannot
        fix. The paged path must rollback + host-fold (rings resolve
        into props) and end equal to the oracle, props included."""
        paged = MergeLaneStore()
        key = ("doc", "s", "anno")
        schedule = [("insert", 0, "abcdef", 0, GOD_CLIENT, 1)]
        for i in range(6):  # DEFAULT_ANNO_SLOTS=4 -> ring exhausts
            schedule.append(("annotate", 0, 6, {f"k{i}": i}, 1,
                             GOD_CLIENT, 2 + i))
        paged.apply({key: _stream(paged.builder, schedule)})
        assert paged.paged_rescues >= 1
        _, content = _oracle_content(schedule)
        assert paged.text(key) == "abcdef"
        assert flatten_snapshot_content(paged.extract_all()[key]) \
            == content

    def test_mid_burst_ring_overflow_rolls_back_and_rescues(self):
        """Ring exhaustion in a LATER chunk of a scanned burst: the
        overflow flag is sticky across the scan carry, the flagged doc
        rolls back to the retained PRE-BURST view, and the host rescue
        re-applies the full stream — content equal to the oracle."""
        schedule = []
        seq = 0
        for _ in range(300):  # > max_t -> K=2 scanned chunks
            seq += 1
            schedule.append(("insert", 0, "y", seq - 1, GOD_CLIENT, seq))
        for i in range(6):  # ring exhausts in chunk 2
            seq += 1
            schedule.append(("annotate", 0, 4, {f"k{i}": i}, seq - 1,
                             GOD_CLIENT, seq))

        paged = MergeLaneStore()
        key = ("d", "s", "burst-anno")
        paged.apply({key: _stream(paged.builder, schedule)})
        assert paged.paged_rescues >= 1
        oracle, content = _oracle_content(schedule)
        assert paged.text(key) == oracle.get_text()
        assert flatten_snapshot_content(paged.extract_all()[key]) \
            == content

    def test_seed_then_apply_matches_bucketed(self):
        """Snapshot-seeded lanes (attach-time content) bootstrap into
        pages — any size, no fit check — and serve follow-on ops
        exactly, props included."""
        entries = [{"text": "hello paged world", "props": {"x": 1}}]
        paged = MergeLaneStore(page_rows=4)
        key = ("doc", "s", "seeded")
        assert paged.seed(key, entries, 0, 4)
        paged.apply({key: [paged.builder.remove(0, 6, 4, GOD_CLIENT, 5)]})
        assert paged.text(key) == "paged world"
        assert flatten_snapshot_content(paged.extract_all()[key]) == \
            [(ch, (("x", 1),)) for ch in "paged world"]
        # A seed larger than one page spans several, no opaque fallback.
        big = ("doc", "s", "big")
        assert paged.seed(big, [{"text": "z"}] * 9, 0, 4)
        assert len(paged.pages.tables[big]) == pages_for(9, 4)
        assert paged.text(big) == "z" * 9

    def test_fragmentation_then_compact_releases_pages(self):
        """Insert deep, remove most, advance the MSN past the removes:
        the budgeted page-granular zamboni left-packs the survivors and
        the trailing release returns the emptied pages to the pool —
        text untouched."""
        store = MergeLaneStore(page_rows=16)
        key = ("doc", "s", "frag")
        b = store.builder
        ops = []
        seq = 0
        for i in range(80):
            seq += 1
            ops.append(b.insert_text(i, "x", seq - 1, GOD_CLIENT, seq))
        store.apply({key: ops})
        pages_before = len(store.pages.tables[key])
        assert pages_before >= 5
        seq += 1
        # One remove of almost everything, msn stamped PAST it so the
        # tombstones are zamboni-eligible immediately.
        store.apply({key: [b.remove(0, 76, seq - 1, GOD_CLIENT, seq,
                                    msn=seq)]})
        store._compact_tick_paged()
        assert store.text(key) == "xxxx"
        assert len(store.pages.tables[key]) < pages_before
        assert store.pages.counts[key] <= 16
        assert store.page_compactions >= 1

    def test_warm_paged_apply_does_not_retrace(self):
        """Same (docs, pages, T) shape applied repeatedly must hit the
        jit cache: pow2 padding is the retrace bound, probed as
        kernel.paged_apply.* (the static rule's runtime cross-check)."""
        store = MergeLaneStore()
        b = store.builder
        seq = 0

        def wave():
            nonlocal seq
            out = {}
            for d in range(3):
                key = ("doc", "s", f"w{d}")
                ops = []
                for _ in range(2):
                    seq += 1
                    ops.append(b.insert_text(0, "a", seq - 1, GOD_CLIENT,
                                             seq))
                out[key] = ops
            return out

        store.apply(wave())  # compile
        counters.reset()
        for _ in range(4):
            store.apply(wave())
        assert counters.get("kernel.paged_apply.retraces") == 0


# ---------------------------------------------------------------------------
# paged serving end to end (object path through TpuSequencerLambda)
# ---------------------------------------------------------------------------

class _Ctx:
    def checkpoint(self, *_):
        pass

    def error(self, err, restart=False):
        raise err


def _lam(emit):
    return TpuSequencerLambda(
        _Ctx(), emit=emit, nack=lambda *a: None, client_timeout_s=0.0)


def _qm(offset, doc, box):
    return QueuedMessage(topic="rawdeltas", partition=0, offset=offset,
                         key=doc, value=boxcar_to_wire(box))


def _join(cid):
    return DocumentMessage(0, -1, MessageType.CLIENT_JOIN,
                           data=json.dumps({"clientId": cid,
                                            "detail": {}}))


def _insert(csn, pos, text):
    from fluidframework_tpu.mergetree.client import OP_INSERT
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=csn - 1,
        type=MessageType.OPERATION,
        contents={"address": "s", "contents": {
            "address": "t", "contents": {
                "type": OP_INSERT, "pos1": pos, "seg": {"text": text}}}})


def _emit_key(doc_id, m):
    return (doc_id, m.sequence_number, m.minimum_sequence_number,
            m.client_id, m.client_sequence_number)


def _waves(n_waves=5, docs=3, storm_ops=6, fleet_ops=1):
    waves = []
    csn = {d: 0 for d in range(docs)}
    for w in range(n_waves):
        wave = []
        for d in range(docs):
            doc = f"p{d}"
            n = storm_ops if d == 0 else fleet_ops
            msgs = [] if w else [_join(f"c{d}")]
            for _ in range(n):
                csn[d] += 1
                msgs.append(_insert(csn[d], 0, f"{csn[d] % 10}"))
            wave.append((doc, Boxcar("t", doc, f"c{d}", msgs)))
        waves.append(wave)
    return waves


def _drive(stall=None):
    emits = []
    lam = _lam(lambda doc, m: emits.append(_emit_key(doc, m)))
    if stall is not None:
        lam.stall_hook = stall
    off = 0
    for wave in _waves():
        for doc, box in wave:
            lam.handler_raw(_qm(off, doc, box))
            off += 1
        lam.flush()
    lam.drain()
    texts = {d: lam.channel_text(d, "s", "t") for d in ("p0", "p1", "p2")}
    return lam, emits, texts


def _scalar_emits():
    """The same waves through the scalar DeliLambda (object boxcars):
    the sequencing reference the device path is held to."""
    from fluidframework_tpu.server.lambdas.deli import DeliLambda
    out = []
    lam = DeliLambda(_Ctx(), emit=lambda d, m: out.append(_emit_key(d, m)),
                     nack=lambda *a: None)
    off = 0
    for wave in _waves():
        for doc, box in wave:
            lam.handler(QueuedMessage(topic="rawdeltas", partition=0,
                                      offset=off, key=doc, value=box))
            off += 1
    return out


class TestPagedServing:
    def test_paged_sequencer_emits_identical_to_bucketed(self):
        """The serving contract: per-document emit streams (order
        included) equal the scalar DeliLambda's, and each channel's
        materialized text equals the oracle applying the sequenced ops
        (inserts at 0 from one client: the digits newest first)."""
        lam, emits_p, texts_p = _drive()
        emits_s = _scalar_emits()
        for d in ("p0", "p1", "p2"):
            assert [e for e in emits_p if e[0] == d] == \
                [e for e in emits_s if e[0] == d], d
        for d, ops in (("p0", 30), ("p1", 5), ("p2", 5)):
            oracle = MergeTreeOracle(local_client=GOD)
            for seq, csn in [(e[1], e[4]) for e in emits_s
                             if e[0] == d and e[3] is not None]:
                oracle.insert_text(0, f"{csn % 10}", seq - 1, 1, seq)
                oracle.update_seq(seq)
            assert texts_p[d] == oracle.get_text() == "".join(
                f"{i % 10}" for i in range(ops, 0, -1))
        assert lam.merge.paged_stats()["pages_in_use"] >= 1

    def test_faultplan_paged_serving_run_twice_deterministic(self):
        """A seeded FaultPlan stalling the flush must reproduce the
        paged serving run bit-identically: same fault trace
        fingerprint, same emitted stream, same channel text, same page
        bookkeeping."""
        from fluidframework_tpu.testing import faultinject

        def once():
            plan = faultinject.FaultPlan(seed=4242, stall=1.0,
                                         stall_range_ms=(0.05, 0.2))
            lam, emits, texts = _drive(
                stall=lambda: faultinject.stall(plan))
            return (emits, texts, plan.fingerprint(),
                    lam.merge.paged_stats())

        emits_a, texts_a, fp_a, stats_a = once()
        emits_b, texts_b, fp_b, stats_b = once()
        assert fp_a == fp_b
        assert emits_a == emits_b
        assert texts_a == texts_b
        assert stats_a == stats_b


# ---------------------------------------------------------------------------
# R10 megakernel: the paged fast flush dispatches ONE serve_megakernel
# ring per flush (page-group jobs, no bucket grid), and the pallas
# program must be indistinguishable from the scan op-phase it replaces.
# ---------------------------------------------------------------------------

def _annotate(csn, start, end, props):
    from fluidframework_tpu.mergetree.client import OP_ANNOTATE
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=csn - 1,
        type=MessageType.OPERATION,
        contents={"address": "s", "contents": {
            "address": "t", "contents": {
                "type": OP_ANNOTATE, "pos1": start, "pos2": end,
                "props": props}}})


def _drive_mega(interpret, waves=None):
    """Paged raw-wire drive with the megakernel op-phase mode pinned:
    interpret=True runs the pallas program (interpret mode on CPU),
    False the counted scan fallback path inside the same dispatch."""
    emits = []
    lam = _lam(lambda doc, m: emits.append(_emit_key(doc, m)))
    lam.megakernel_interpret = interpret
    off = 0
    for wave in (waves if waves is not None else _waves()):
        for doc, box in wave:
            lam.handler_raw(_qm(off, doc, box))
            off += 1
        lam.flush()
    lam.drain()
    docs = sorted({doc for wave in (waves or _waves())
                   for doc, _ in wave})
    texts = {d: lam.channel_text(d, "s", "t") for d in docs}
    return lam, emits, texts


class TestMegakernelServing:
    def test_interpret_megakernel_planes_bit_identical_to_scan(self):
        """The acceptance gate: on a contended ragged fleet every ring
        the pallas program emits — the full narrow int16 plane AND the
        msn plane — must be bit-identical to the scan op-phase run on
        the very same staged inputs, and the final emit stream/channel
        text must match a scan-mode drive."""
        from fluidframework_tpu.server import serve_step

        real = serve_step.serve_megakernel
        keep = serve_step.serve_megakernel_keep
        modes, plane_ok = [], []

        def paired(tstate, pool, lww, tx, pids, cts, mns, sqs,
                   mxs, lxs, rxs, fused, stats):
            # Non-donating scan reference FIRST so the real call can
            # still consume its buffers.
            ref = keep(tstate, pool, lww, tx, pids, cts, mns, sqs,
                       mxs, lxs, rxs, False, stats)
            out = real(tstate, pool, lww, tx, pids, cts, mns, sqs,
                       mxs, lxs, rxs, fused, stats)
            modes.append(fused)
            plane_ok.append(
                np.array_equal(np.asarray(ref[3]), np.asarray(out[3]))
                and np.array_equal(np.asarray(ref[4]),
                                   np.asarray(out[4])))
            return out

        serve_step.serve_megakernel = paired
        try:
            _, emits_i, texts_i = _drive_mega(interpret=True)
        finally:
            serve_step.serve_megakernel = real
        _, emits_s, texts_s = _drive_mega(interpret=False)

        assert modes and all(m == "interpret" for m in modes)
        assert all(plane_ok)
        assert emits_i == emits_s
        assert texts_i == texts_s

    def test_megakernel_overflow_rolls_back_and_rescues(self):
        """Annotate-ring exhaustion inside a megakernel ring — the one
        overflow class page pre-growth cannot prevent: the flagged doc
        rolls back to its retained pre-ring view, the host rescue
        re-applies the op stream, and the run stays identical to the
        object path (per-window applies + the store's own rescue) —
        emits, text, and snapshot content with the annotates' props."""
        def waves():
            out = []
            csn = {d: 0 for d in range(3)}
            for w in range(3):
                wave = []
                for d in range(3):
                    doc = f"a{d}"
                    msgs = [] if w else [_join(f"c{d}")]
                    csn[d] += 1
                    msgs.append(_insert(csn[d], 0, "abcdef"))
                    if d == 0 and w == 1:
                        for i in range(6):  # DEFAULT_ANNO_SLOTS=4
                            csn[d] += 1
                            msgs.append(
                                _annotate(csn[d], 0, 6, {f"k{i}": i}))
                    wave.append((doc, Boxcar("t", doc, f"c{d}", msgs)))
                out.append(wave)
            return out

        counters.reset()
        lam_p, emits_p, texts_p = _drive_mega(interpret=False,
                                              waves=waves())
        assert counters.get("serving.recovery_dispatches") >= 1
        assert lam_p.merge.paged_rescues >= 1

        emits_ref = []
        lam_o = _lam(lambda doc, m: emits_ref.append(_emit_key(doc, m)))
        off = 0
        for wave in waves():
            for doc, box in wave:
                lam_o.handler(QueuedMessage(topic="rawdeltas", partition=0,
                                            offset=off, key=doc, value=box))
                off += 1
            lam_o.flush()
        texts_ref = {d: lam_o.channel_text(d, "s", "t")
                     for d in ("a0", "a1", "a2")}
        assert emits_p == emits_ref
        assert texts_p == texts_ref
        snap_p = lam_p.summarize_documents()[("a0", "s", "t")]
        snap_o = lam_o.summarize_documents()[("a0", "s", "t")]
        assert flatten_snapshot_content(snap_p) == \
            flatten_snapshot_content(snap_o)

    def test_megakernel_path_fills_every_serving_sub_span(self):
        """Span coverage parity (docs/observability.md v3): the
        megakernel ring path must attribute its flush cost to the same
        named serving sub-spans as the scan path — pack, dispatch,
        readback — plus its own settle stage (paged-group scalar
        adoption/rescue), so ring captures never hide a stage inside
        the flush total. The hist= histograms are always-on, so
        coverage is assertable without enabling trace sampling."""
        counters.reset()
        try:
            _, emits, _ = _drive_mega(interpret=False)
            assert emits
            assert counters.get("serving.megakernel_rings") >= 1
            for stage in ("serving.pack", "serving.dispatch",
                          "serving.readback", "serving.settle"):
                assert counters.latency_window(stage), stage
        finally:
            counters.reset()

    def test_device_stats_reconcile_exactly_on_megakernel_path(self):
        """PR 12's contract carried into R10: the stats plane rides
        the megakernel readback and every countable device slot equals
        its host mirror EXACTLY — including the merge op count, which
        the paged tail reports in int32 halves (the int16 occupancy
        plane may wrap on deep groups)."""
        from fluidframework_tpu.telemetry import device_stats

        prev = device_stats.enabled()
        device_stats.set_enabled(True)
        counters.reset()
        try:
            _, emits, _ = _drive_mega(interpret=False)
            assert emits
            assert counters.get("serving.megakernel_rings") >= 1
            assert device_stats.reconcile() is None
            snap = counters.snapshot()
            for slot in device_stats.SERVE_SLOTS:
                dev = snap.get(f"device.serving.{slot}")
                host = snap.get(f"host.serving.{slot}")
                assert dev == host, (slot, dev, host)
        finally:
            device_stats.set_enabled(prev)
            counters.reset()
