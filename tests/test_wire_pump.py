"""The native wire->tensor pump + fast serving flush.

The fast path (tpu_sequencer.handler_raw -> _flush_raw) must be
indistinguishable from the object path (handler -> _flush_window) for any
traffic: same emitted messages, same nacks, same materialized state, same
checkpoints. These tests drive both lambdas with identical traffic — the
object path as the oracle (itself differential-tested against the scalar
deli in test_tpu_serving.py) — and poke the shapes that must FALL BACK
(leaves, group ops, items payloads, malformed frames).

Reference analog: deli/lambda.ts ticket tests + the kafka wire format
contract in services-core (extractBoxcar)."""

import json

import numpy as np
import pytest

from fluidframework_tpu.mergetree.client import (
    OP_ANNOTATE,
    OP_GROUP,
    OP_INSERT,
    OP_REMOVE,
)
from fluidframework_tpu.protocol.messages import (
    Boxcar,
    DocumentMessage,
    MessageType,
)
from fluidframework_tpu.server import pump as pump_mod
from fluidframework_tpu.server.log import QueuedMessage
from fluidframework_tpu.server.tpu_sequencer import TpuSequencerLambda
from fluidframework_tpu.server.wire import boxcar_from_wire, boxcar_to_wire

pytestmark = pytest.mark.skipif(not pump_mod.available(),
                                reason="native wirepump unavailable")


class _Ctx:
    def checkpoint(self, *_):
        pass

    def error(self, err, restart=False):
        raise err


def _lam(emit, nack, **kw):
    kw.setdefault("client_timeout_s", 0.0)
    return TpuSequencerLambda(_Ctx(), emit=emit, nack=nack, **kw)


def _qm(offset, doc, box, raw=False):
    value = boxcar_to_wire(box) if raw else box
    return QueuedMessage(topic="rawdeltas", partition=0, offset=offset,
                         key=doc, value=value)


def _merge_op(csn, op):
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=csn - 1,
        type=MessageType.OPERATION,
        contents={"address": "s", "contents": {"address": "t",
                                               "contents": op}})


def _lww_op(csn, op, chan="m"):
    return DocumentMessage(
        client_sequence_number=csn, reference_sequence_number=csn - 1,
        type=MessageType.OPERATION,
        contents={"address": "s", "contents": {"address": chan,
                                               "contents": op}})


def _join(cid):
    return DocumentMessage(0, -1, MessageType.CLIENT_JOIN,
                           data=json.dumps({"clientId": cid,
                                            "detail": {}}))


def _emit_key(doc_id, m):
    return (doc_id, m.sequence_number, m.minimum_sequence_number, m.type,
            m.client_id, m.client_sequence_number,
            m.reference_sequence_number,
            json.dumps(m.contents, sort_keys=True), m.data)


def run_both(traffic, **kw):
    """traffic: list of (doc_id, Boxcar). Returns (A, B, emits, nacks)
    where A took the object path and B the raw-bytes fast path."""
    ea, na, eb, nb = [], [], [], []
    A = _lam(lambda d, m: ea.append(_emit_key(d, m)),
             lambda d, c, n: na.append((d, c, n.content.code)), **kw)
    B = _lam(lambda d, m: eb.append(_emit_key(d, m)),
             lambda d, c, n: nb.append((d, c, n.content.code)), **kw)
    assert B._pump is not None
    for i, (doc, box) in enumerate(traffic):
        A.handler(_qm(i, doc, box))
        B.handler_raw(_qm(i, doc, box, raw=True))
    A.flush()
    B.flush()
    return A, B, (ea, eb), (na, nb)


def assert_equivalent(A, B, emits, nacks, channels=()):
    ea, eb = emits
    assert sorted(ea) == sorted(eb)
    # Per-doc emit order must match exactly (cross-doc order is the
    # sequencer's choice on both paths).
    from collections import defaultdict
    pa, pb = defaultdict(list), defaultdict(list)
    for e in ea:
        pa[e[0]].append(e)
    for e in eb:
        pb[e[0]].append(e)
    assert pa == pb
    assert sorted(nacks[0]) == sorted(nacks[1])
    for doc, store, chan in channels:
        assert A.channel_text(doc, store, chan) == \
            B.channel_text(doc, store, chan)
        assert A.channel_snapshot(doc, store, chan) == \
            B.channel_snapshot(doc, store, chan)


class TestWireCodec:
    def test_boxcar_roundtrip(self):
        box = Boxcar("t", "doc-α", "c✓1", [
            _join("c✓1"), _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                                        "seg": {"text": "héllo\n"}})])
        out = boxcar_from_wire(boxcar_to_wire(box))
        assert out.document_id == "doc-α" and out.client_id == "c✓1"
        assert out.contents[1].contents["contents"]["contents"][
            "seg"]["text"] == "héllo\n"


class TestFastSlowDifferential:
    def test_mixed_families_match(self):
        traffic = []
        for d in range(6):
            doc = f"d{d}"
            msgs = [_join(f"c{d}")]
            csn = 1
            for i in range(5):
                msgs.append(_merge_op(csn, {
                    "type": OP_INSERT, "pos1": 0,
                    "seg": {"text": f"t{i}✓"}}))
                csn += 1
            msgs.append(_merge_op(csn, {"type": OP_REMOVE, "pos1": 1,
                                        "pos2": 3}))
            csn += 1
            msgs.append(_merge_op(csn, {
                "type": OP_ANNOTATE, "pos1": 0, "pos2": 2,
                "props": {"bold": True, "size": 12}}))
            csn += 1
            msgs.append(_merge_op(csn, {
                "type": OP_INSERT, "pos1": 0,
                "seg": {"marker": True, "props": {"tag": "h1"}}}))
            csn += 1
            msgs.append(_lww_op(csn, {"type": "set", "key": "k你",
                                      "value": {"deep": [1, None]},
                                      "pid": "p"}))
            csn += 1
            msgs.append(_lww_op(csn, {"type": "increment", "delta": 41},
                                chan="n"))
            traffic.append((doc, Boxcar("t", doc, f"c{d}", msgs)))
        A, B, emits, nacks = run_both(traffic)
        assert not nacks[0] and not nacks[1]
        chans = [(f"d{d}", "s", c) for d in range(6)
                 for c in ("t", "m", "n")]
        assert_equivalent(A, B, emits, nacks, chans)
        snap = B.channel_snapshot("d0", "s", "m")
        assert snap["entries"]["k你"] == {"deep": [1, None]}

    def test_leave_routes_slow_and_matches(self):
        msgs = [_join("c0"), _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                                           "seg": {"text": "abc"}}),
                DocumentMessage(0, -1, MessageType.CLIENT_LEAVE,
                                data=json.dumps({"clientId": "c0"}))]
        A, B, emits, nacks = run_both([("d0", Boxcar("t", "d0", "c0",
                                                     msgs))])
        # leave + the NoClient the empty table triggers, on BOTH paths
        types_a = [e[3] for e in emits[0]]
        assert MessageType.CLIENT_LEAVE in types_a
        assert MessageType.NO_CLIENT in types_a
        assert_equivalent(A, B, emits, nacks, [("d0", "s", "t")])

    def test_group_and_items_fall_back(self):
        msgs = [_join("c0"),
                _merge_op(1, {"type": OP_GROUP, "ops": [
                    {"type": OP_INSERT, "pos1": 0,
                     "seg": {"text": "xy"}}]}),
                _merge_op(2, {"type": OP_INSERT, "pos1": 0,
                              "seg": {"items": [1, 2, 3]}})]
        A, B, emits, nacks = run_both([("d0", Boxcar("t", "d0", "c0",
                                                     msgs))])
        assert_equivalent(A, B, emits, nacks, [("d0", "s", "t")])
        # Round 5: items MATERIALIZE on the lanes (extraction re-encodes
        # them) — the fast path still routes the doc slow, and both
        # paths end with the same lane content, not an opaque drop.
        assert ("d0", "s", "t") not in A.merge.opaque
        assert ("d0", "s", "t") not in B.merge.opaque
        assert A.channel_items("d0", "s", "t") == \
            B.channel_items("d0", "s", "t") == [1, 2, 3]

    def test_stale_refseq_nacks_match(self):
        msgs = [_join("c0")]
        for i in range(1, 4):
            msgs.append(_merge_op(i, {"type": OP_INSERT, "pos1": 0,
                                      "seg": {"text": "x"}}))
        bad = DocumentMessage(
            client_sequence_number=4, reference_sequence_number=-5,
            type=MessageType.OPERATION,
            contents={"address": "s", "contents": {
                "address": "t", "contents": {"type": OP_INSERT, "pos1": 0,
                                             "seg": {"text": "y"}}}})
        msgs.append(bad)
        A, B, emits, nacks = run_both(
            [("d0", Boxcar("t", "d0", "c0", msgs))])
        assert len(nacks[0]) == 1 and nacks[0] == nacks[1]
        assert_equivalent(A, B, emits, nacks, [("d0", "s", "t")])

    def test_unjoined_client_nacks_match(self):
        msgs = [_merge_op(1, {"type": OP_INSERT, "pos1": 0,
                              "seg": {"text": "x"}})]
        A, B, emits, nacks = run_both(
            [("d0", Boxcar("t", "d0", "ghost", msgs))])
        assert len(nacks[0]) == 1 and nacks[0] == nacks[1]
        assert not emits[0] and not emits[1]

    def test_malformed_boxcar_drops_without_killing_the_lambda(self):
        """An undecodable log record is deterministic poison (redelivery
        can never fix it): the frame drops with a logged counter and the
        lambda keeps serving — innocent traffic in the same flush and
        after it is unaffected (round-5 containment; previously the
        whole flush aborted)."""
        eb, nb = [], []
        B = _lam(lambda d, m: eb.append((d, m)), lambda *a: nb.append(a))
        B.handler_raw(QueuedMessage(
            topic="rawdeltas", partition=0, offset=0, key="d0",
            value=b'{"documentId": "d0", "contents": [{{{'))
        # Invalid UTF-8 takes the same frame-fallback road (the native
        # pump gates whole buffers up front).
        B.handler_raw(QueuedMessage(
            topic="rawdeltas", partition=0, offset=1, key="d0",
            value=b'{"documentId": "d0\x81", "contents": []}'))
        good = Boxcar("t", "d0", "c0", [
            _join("c0"), _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                                       "seg": {"text": "ok"}})])
        B.handler_raw(_qm(2, "d0", good, raw=True))
        B.flush()
        B.drain()
        assert B.poison_frames == 2
        assert len(eb) == 2  # join + the good op sequenced
        assert B.channel_text("d0", "s", "t") == "ok"

    def test_lax_json_payload_poisons_at_ingest_not_materialization(self):
        """Payload spans the pump admits are re-parsed host-side with
        STRICT json.loads (host.py MergeArenaBlock.resolve, _props), so
        the native tokenizers must be exactly as strict: a frame that is
        lax-parseable but strict-invalid ('1.2.3', leading zeros, bad
        escapes) must fall back whole and hit the slow path's poison
        containment at INGEST — previously it was admitted natively and
        planted a deferred JSONDecodeError that crashed every later
        read/summarize of the lane."""
        eb, nb = [], []
        B = _lam(lambda d, m: eb.append((d, m)), lambda *a: nb.append(a))
        # Seed a healthy items channel first.
        good = Boxcar("t", "d0", "c0", [
            _join("c0"),
            _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                          "seg": {"items": [7, 8]}})])
        B.handler_raw(_qm(0, "d0", good, raw=True))

        # Craft lax frames by byte-surgery on a valid wire frame: the
        # placeholder array is replaced with shapes json.loads rejects.
        def lax_frame(csn, payload: bytes, seg_key="items"):
            box = Boxcar("t", "d0", "c0", [
                _merge_op(csn, {"type": OP_INSERT, "pos1": 0,
                                "seg": {seg_key: [123456789]}})])
            raw = boxcar_to_wire(box)
            assert raw.count(b"[123456789]") == 1
            return raw.replace(b"[123456789]", payload)

        for off, payload in enumerate(
                (b"[1.2.3]", b"[01]", b'["\\x"]', b"[1e]"), start=1):
            B.handler_raw(QueuedMessage(
                topic="rawdeltas", partition=0, offset=off, key="d0",
                value=lax_frame(off, payload)))
        # Lax props on a text insert take the same road.
        box = Boxcar("t", "d0", "c0", [
            _merge_op(5, {"type": OP_INSERT, "pos1": 0,
                          "seg": {"text": "x", "props": {"a": [123456789]}}})])
        raw = boxcar_to_wire(box).replace(b"[123456789]", b"[01]")
        B.handler_raw(QueuedMessage(topic="rawdeltas", partition=0,
                                    offset=5, key="d0", value=raw))
        # Innocent traffic after the poison still lands...
        B.handler_raw(_qm(6, "d0", Boxcar("t", "d0", "c0", [
            _merge_op(2, {"type": OP_INSERT, "pos1": 2,
                          "seg": {"items": [9]}})]), raw=True))
        B.flush()
        B.drain()
        assert B.poison_frames == 5
        # ...and every later read path materializes without a deferred
        # JSONDecodeError (the round-5 crash: resolve() on the lax span).
        assert B.channel_items("d0", "s", "t") == [7, 8, 9]
        assert ("d0", "s", "t") not in B.merge.opaque

    def test_multi_wave_interleaving_matches(self):
        rng = np.random.default_rng(7)
        docs = [f"w{d}" for d in range(4)]
        offset = 0
        traffic = []
        csn = {d: 0 for d in docs}
        for wave in range(3):
            for d in docs:
                msgs = []
                if wave == 0:
                    msgs.append(_join(f"c-{d}"))
                for _ in range(int(rng.integers(1, 6))):
                    csn[d] += 1
                    r = rng.random()
                    if r < 0.5:
                        msgs.append(_merge_op(csn[d], {
                            "type": OP_INSERT,
                            "pos1": int(rng.integers(0, 3)),
                            "seg": {"text": "ab"}}))
                    elif r < 0.7:
                        msgs.append(_lww_op(csn[d], {
                            "type": "set", "key": f"k{rng.integers(3)}",
                            "value": int(rng.integers(100)),
                            "pid": "p"}))
                    else:
                        msgs.append(_lww_op(csn[d], {
                            "type": "increment", "delta": 1}, chan="n"))
                traffic.append((d, Boxcar("t", d, f"c-{d}", msgs)))
                offset += 1
        # Feed wave-by-wave with a flush between (multiple fast flushes).
        ea, na, eb, nb = [], [], [], []
        A = _lam(lambda d, m: ea.append(_emit_key(d, m)),
                 lambda d, c, n: na.append((d, c)))
        B = _lam(lambda d, m: eb.append(_emit_key(d, m)),
                 lambda d, c, n: nb.append((d, c)))
        for i, (doc, box) in enumerate(traffic):
            A.handler(_qm(i, doc, box))
            B.handler_raw(_qm(i, doc, box, raw=True))
            if i % 4 == 3:
                A.flush()
                B.flush()
        A.flush()
        B.flush()
        assert not na and not nb
        assert_equivalent(A, B, ((ea), (eb)), (na, nb),
                          [(d, "s", c) for d in docs
                           for c in ("t", "m", "n")])


class TestPipelinedDrain:
    def test_pipelined_matches_sync(self):
        """pipelined=True defers each clean window's fetch/emit to the
        next flush (or drain()); the observable stream must be identical
        to synchronous mode."""
        def waves():
            out = []
            for w in range(4):
                for d in range(3):
                    doc = f"p{d}"
                    msgs = [] if w else [_join(f"c{d}")]
                    base = w * 3
                    for i in range(3):
                        msgs.append(_merge_op(base + i + 1, {
                            "type": OP_INSERT, "pos1": 0,
                            "seg": {"text": f"{w}{i}"}}))
                    out.append((w, doc, Boxcar("t", doc, f"c{d}", msgs)))
            return out

        ea, eb = [], []
        A = _lam(lambda d, m: ea.append(_emit_key(d, m)),
                 lambda *a: None)
        B = _lam(lambda d, m: eb.append(_emit_key(d, m)),
                 lambda *a: None)
        B.pipelined = True
        off = 0
        last_wave = 0
        for w, doc, box in waves():
            if w != last_wave:
                A.flush()
                B.flush()
                last_wave = w
            A.handler(_qm(off, doc, box))
            B.handler_raw(_qm(off, doc, box, raw=True))
            off += 1
        A.flush()
        B.flush()
        B.drain()  # settle the final deferred window
        assert sorted(ea) == sorted(eb)
        for d in range(3):
            assert A.channel_text(f"p{d}", "s", "t") == \
                B.channel_text(f"p{d}", "s", "t")

    def test_pipelined_recovery_still_converges(self):
        from fluidframework_tpu.server.tpu_sequencer import MergeLaneStore
        B = _lam(lambda *a: None, lambda *a: None,
                 merge_store=MergeLaneStore(page_rows=4))
        B.pipelined = True
        csn = 0
        for w in range(4):
            msgs = [] if w else [_join("c0")]
            for _ in range(6):
                csn += 1
                msgs.append(_merge_op(csn, {"type": OP_INSERT, "pos1": 0,
                                            "seg": {"text": f"{csn%10}"}}))
            B.handler_raw(_qm(w, "pp", Boxcar("t", "pp", "c0", msgs),
                              raw=True))
            B.flush()
        B.drain()
        # The lane grew page by page across the deferred flushes.
        assert len(B.merge.pages.tables[("pp", "s", "t")]) > 1
        assert B.channel_text("pp", "s", "t") == "".join(
            f"{i%10}" for i in range(24, 0, -1))


class TestPipelinedCheckpointOffsets:
    def test_drain_commits_only_its_windows_offsets(self):
        """A deferred window's drain must commit the offsets it covered —
        not offsets staged afterward for a window that has not sequenced
        yet (at-least-once: a crash must replay the staged backlog)."""
        commits = []

        class Ctx(_Ctx):
            def checkpoint(self, offset):
                commits.append(offset)

        B = TpuSequencerLambda(Ctx(), emit=lambda *a: None,
                               nack=lambda *a: None,
                               client_timeout_s=0.0)
        B.pipelined = True
        B.handler_raw(_qm(0, "d0", Boxcar("t", "d0", "c0", [
            _join("c0"), _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                                       "seg": {"text": "a"}})]),
            raw=True))
        B.flush()  # deferred: no checkpoint yet
        assert commits == []
        # Stage (but do not flush) a newer offset.
        B.handler_raw(_qm(7, "d0", Boxcar("t", "d0", "c0", [
            _merge_op(2, {"type": OP_INSERT, "pos1": 1,
                          "seg": {"text": "b"}})]), raw=True))
        B.drain()
        assert commits == [0], commits  # NOT 7
        B.flush()
        B.drain()
        assert commits[-1] == 7


class TestInternSyncAcrossPaths:
    def test_slow_path_interned_client_does_not_desync_pump(self):
        """A client interned by the SLOW path (fallback join) must be
        preloaded into the pump before the next fast parse, or the pump
        would hand its ordinal to a different client."""
        emits = []
        B = _lam(lambda d, m: emits.append((m.client_id,
                                            m.sequence_number, m.type)),
                 lambda *a: None)
        # Join with NO data payload: the pump cannot extract the joining
        # client id -> whole-doc fallback; slow path interns via the
        # boxcar sender (ordinal 0 host-side only).
        B.handler_raw(_qm(0, "d0", Boxcar("t", "d0", "cA", [
            DocumentMessage(0, -1, MessageType.CLIENT_JOIN),
            _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                          "seg": {"text": "a"}})]), raw=True))
        B.flush()
        # Second client joins via the FAST path: without the re-sync the
        # pump would also assign ordinal 0 to cB.
        B.handler_raw(_qm(1, "d0", Boxcar("t", "d0", "cB", [
            _join("cB"),
            _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                          "seg": {"text": "b"}})]), raw=True))
        B.flush()
        # Both clients' ops sequenced and attributed correctly.
        ops = [(c, s) for c, s, t in emits if t == MessageType.OPERATION]
        assert ops == [("cA", 2), ("cB", 4)], emits
        dl = B.docs["d0"]
        assert dl.interner["cA"] != dl.interner["cB"]


class TestFastOverflowRecovery:
    def test_promotion_through_buckets_on_fast_path(self):
        """A lane that outgrows its page mid-flush grows by appending
        pages before the megakernel dispatches (flush_lane_for), so the
        fast path needs no overflow recovery for growth."""
        from fluidframework_tpu.server.tpu_sequencer import MergeLaneStore
        eb = []
        B = _lam(lambda d, m: eb.append(1), lambda *a: None,
                 merge_store=MergeLaneStore(page_rows=4))
        msgs = [_join("c0")]
        for i in range(1, 25):
            msgs.append(_merge_op(i, {"type": OP_INSERT, "pos1": 0,
                                      "seg": {"text": f"{i%10}"}}))
        B.handler_raw(_qm(0, "grow", Boxcar("t", "grow", "c0", msgs),
                          raw=True))
        B.flush()
        key = ("grow", "s", "t")
        assert key in B.merge.where
        assert len(B.merge.pages.tables[key]) > 1, "lane never grew"
        assert B.merge.paged_rescues == 0 and not B.merge.opaque
        text = B.channel_text("grow", "s", "t")
        assert text == "".join(f"{i%10}" for i in range(24, 0, -1))

    def test_lww_promotion_on_fast_path(self):
        from fluidframework_tpu.server.tpu_sequencer import LwwLaneStore
        B = _lam(lambda *a: None, lambda *a: None)
        B.lww = LwwLaneStore(capacities=(4, 64))
        msgs = [_join("c0")]
        for i in range(1, 13):
            msgs.append(_lww_op(i, {"type": "set", "key": f"key{i}",
                                    "value": i, "pid": "p"}))
        B.handler_raw(_qm(0, "lw", Boxcar("t", "lw", "c0", msgs),
                          raw=True))
        B.flush()
        snap = B.channel_snapshot("lw", "s", "m")
        assert snap["entries"] == {f"key{i}": i for i in range(1, 13)}
        assert B.lww.where[("lw", "s", "m")][0] == 1


class TestSequencedWindow:
    def _window(self):
        captured = []
        B = _lam(lambda *a: None, lambda *a: None)
        B.emit_window = captured.append
        msgs = [_join("c0"),
                _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                              "seg": {"text": "hello"}}),
                _lww_op(2, {"type": "set", "key": "k", "value": 5,
                            "pid": "p"})]
        B.handler_raw(_qm(0, "d0", Boxcar("t", "d0", "c0", msgs),
                          raw=True))
        B.flush()
        assert len(captured) == 1
        return captured[0]

    def test_lazy_materialization(self):
        w = self._window()
        out = list(w.messages())
        assert len(out) == 3 == len(w)
        types = [m.type for _, m in out]
        assert types == [MessageType.CLIENT_JOIN, MessageType.OPERATION,
                         MessageType.OPERATION]
        seqs = [m.sequence_number for _, m in out]
        assert seqs == [1, 2, 3]
        assert out[1][1].client_id == "c0"
        assert out[0][1].client_id is None  # joins carry no client id

    def test_downstream_lambdas_consume_windows(self):
        from fluidframework_tpu.server.database import (
            DatabaseManager,
        )
        from fluidframework_tpu.server.lambdas.broadcaster import (
            BroadcasterLambda,
        )
        from fluidframework_tpu.server.lambdas.scriptorium import (
            ScriptoriumLambda,
            delta_key,
            query_deltas,
        )
        w = self._window()
        db = DatabaseManager()
        deltas = db.collection("deltas", unique_key=delta_key,
                               range_key=delta_key)
        sc = ScriptoriumLambda(_Ctx(), deltas)
        sc.handler(QueuedMessage("deltas", 0, 0, "__window__", w))
        rows = query_deltas(deltas, "d0")
        assert [r["sequence_number"] for r in rows] == [1, 2, 3]

        got = []
        bc = BroadcasterLambda(_Ctx())
        bc.join_room("d0", got.append)
        bc.handler(QueuedMessage("deltas", 0, 1, "__window__", w))
        assert [m.sequence_number for m in got] == [1, 2, 3]


class TestPumpRestart:
    def test_checkpoint_restart_continues_ordinals(self):
        """Object-path traffic, checkpoint, restart; the new lambda's pump
        preloads the restored client interners so fast-path ordinals keep
        matching the device client table."""
        from fluidframework_tpu.server.database import (
            DatabaseManager,
        )
        db = DatabaseManager()
        ckpt = db.collection("deliCheckpoints")
        ea, eb = [], []
        A = _lam(lambda d, m: ea.append(_emit_key(d, m)),
                 lambda *a: None, checkpoints=ckpt)
        msgs = [_join("c0"),
                _merge_op(1, {"type": OP_INSERT, "pos1": 0,
                              "seg": {"text": "pre"}})]
        A.handler(_qm(0, "d0", Boxcar("t", "d0", "c0", msgs)))
        A.flush()
        A.close()

        B = _lam(lambda d, m: eb.append(_emit_key(d, m)),
                 lambda *a: None, checkpoints=ckpt)
        tail = [_merge_op(2, {"type": OP_INSERT, "pos1": 3,
                              "seg": {"text": "post"}})]
        B.handler_raw(_qm(1, "d0", Boxcar("t", "d0", "c0", tail),
                          raw=True))
        B.flush()
        assert [e[1] for e in eb] == [3]  # continues the seq numbering
        assert eb[0][4] == "c0"  # correct client id via restored interner


class TestFusedServeConformance:
    def test_fused_window_matches_scan_window(self, monkeypatch):
        """serve_megakernel(fused=True) — the VMEM-resident merge apply
        on the serving fast path — is bit-indistinguishable from the scan
        kernel: same emits, same materialized channels. CPU runs the
        Pallas body in interpret mode; on TPU the same test exercises
        Mosaic via tools/tpu_conformance."""
        import functools

        import jax

        from fluidframework_tpu.mergetree import pallas_apply

        if jax.default_backend() != "tpu":
            # CPU: run the Pallas body in interpret mode. On TPU the
            # patch is skipped so the REAL Mosaic kernel is what's
            # conformance-checked.
            monkeypatch.setattr(
                pallas_apply, "apply_ops_fused_pallas",
                functools.partial(pallas_apply.apply_ops_fused_pallas,
                                  interpret=True))

        def traffic():
            out = []
            for d in range(3):
                doc = f"d{d}"
                msgs = [_join(f"c{d}")]
                for i in range(1, 9):
                    if i % 4 == 0:
                        op = {"type": OP_REMOVE, "pos1": 0, "pos2": 2}
                    elif i % 5 == 0:
                        op = {"type": OP_ANNOTATE, "pos1": 0, "pos2": 3,
                              "props": {"b": i}}
                    else:
                        op = {"type": OP_INSERT, "pos1": 0,
                              "seg": {"text": f"x{i}"}}
                    msgs.append(_merge_op(i, op))
                msgs.append(_lww_op(9, {"type": "set", "key": "k",
                                        "value": d}))
                out.append((doc, Boxcar("t", doc, f"c{d}", msgs)))
            return out

        ea, na, eb, nb = [], [], [], []
        A = _lam(lambda d, m: ea.append(_emit_key(d, m)),
                 lambda d, c, n: na.append((d, c, n.content.code)))
        B = _lam(lambda d, m: eb.append(_emit_key(d, m)),
                 lambda d, c, n: nb.append((d, c, n.content.code)))
        A._fused_serve = False
        B._fused_serve = True
        for i, (doc, box) in enumerate(traffic()):
            A.handler_raw(_qm(i, doc, box, raw=True))
            B.handler_raw(_qm(i, doc, box, raw=True))
        A.flush()
        B.flush()
        A.drain()
        B.drain()
        assert_equivalent(A, B, (ea, eb), (na, nb),
                          [(f"d{d}", "s", "t") for d in range(3)])


class TestNarrowResultPacking:
    def test_msn_span_overflow_falls_back_to_exact_plane(self):
        """A catch-up msn jump wider than the int16 delta within one
        window flips msn_ok: the host must refetch the exact int32 msn
        plane (serve_step narrow packing's rare second RPC) and still
        stamp exact msns."""
        import jax.numpy as jnp

        from fluidframework_tpu.mergetree.paging import PagedMergeStore
        from fluidframework_tpu.server import serve_step
        from fluidframework_tpu.server import ticket_kernel as tk

        B, T, K = 1, 2, 4
        tstate = tk.make_ticket_state(K, batch=B)
        # Surgery: one doc deep into its history (seq 50k) with two
        # clients — a laggard at ref 3 and a caught-up one at 49,999.
        tstate = tstate._replace(
            client_ids=jnp.array([[7, 8, -1, -1]], jnp.int32),
            client_ref=jnp.array([[3, 49_999, 2**31 - 1, 2**31 - 1]],
                                 jnp.int32),
            client_cseq=jnp.array([[5, 9, 0, 0]], jnp.int32),
            next_seq=jnp.array([50_000], jnp.int32),
            min_seq=jnp.array([3], jnp.int32),
        )
        cols = np.zeros((4, B, T), np.int32)
        cols[0, 0] = tk.MsgKind.OP
        # op 1 from the laggard (msn stays 3), then the laggard's ref
        # leaps to 49,000: msn jumps by ~49k > int16 within ONE window.
        cols[1, 0] = [7, 7]
        cols[2, 0] = [6, 7]
        cols[3, 0] = [4, 49_000]
        # A one-window megakernel ring with no page groups or LWW
        # buckets: just the ticket pass and the narrow packing.
        pool = PagedMergeStore(pages=2).pool
        out = serve_step.serve_megakernel_keep(
            tstate, pool, (), jnp.asarray(cols)[None], (), (), (), (),
            (), (), (), False, False)
        flat16, msn32 = out[3][0], out[4][0]
        flat = np.asarray(flat16)
        bt = B * T
        p = 3 * bt
        tailbits = flat[p + 4 * B:]
        assert tailbits[0] == 0, "msn_ok should flag the wide span"
        exact = np.asarray(msn32)
        assert exact[0, 0] == 4 and exact[0, 1] == 49_000
        # And the narrow seq deltas still reconstruct exactly.
        next_seq = ((flat[p + B:p + 2 * B].astype(np.int64) << 16)
                    | (flat[p:p + B].astype(np.int64) & 0xFFFF))
        seq_d = flat[:bt].reshape(B, T).astype(np.int64)
        seq = np.where(seq_d >= 0, next_seq[:, None] - seq_d, 0)
        assert seq[0].tolist() == [50_000, 50_001]


class TestServingRunPacking:
    def _burst_traffic(self, prepend=False, docs=2, k=12):
        # A typing burst inside one boxcar: the client's ref is FROZEN
        # (it has processed nothing since) — the packable shape.
        out = []
        for d in range(docs):
            doc = f"d{d}"
            msgs = [_join(f"c{d}")]
            pos = 0
            for i in range(1, k + 1):
                text = chr(96 + i) * 2
                msgs.append(DocumentMessage(
                    client_sequence_number=i,
                    reference_sequence_number=0,
                    type=MessageType.OPERATION,
                    contents={"address": "s", "contents": {
                        "address": "t", "contents": {
                            "type": OP_INSERT, "pos1": pos,
                            "seg": {"text": text}}}}))
                if not prepend:
                    pos += len(text)
            out.append((doc, Boxcar("t", doc, f"c{d}", msgs)))
        return out

    def test_append_bursts_pack_and_match(self):
        A, B, emits, nacks = run_both(self._burst_traffic(prepend=False))
        assert_equivalent(A, B, emits, nacks,
                          [(f"d{d}", "s", "t") for d in range(2)])

    def test_prepend_bursts_pack_and_match(self):
        A, B, emits, nacks = run_both(self._burst_traffic(prepend=True))
        assert_equivalent(A, B, emits, nacks,
                          [(f"d{d}", "s", "t") for d in range(2)])

    def test_runs_actually_fire(self):
        """Guard against the packer silently never-packing: a burst
        window must stage at least one INSERT_RUN slot."""
        from fluidframework_tpu.mergetree.oppack import OpKind
        seen = {"run": False}
        orig = TpuSequencerLambda._build_merge

        def spy(self, parsed, rows, lanes, slot, *a):
            jobs = orig(self, parsed, rows, lanes, slot, *a)
            for j in jobs:
                if (j["cols"][0] == OpKind.INSERT_RUN).any():
                    seen["run"] = True
            return jobs

        TpuSequencerLambda._build_merge = spy
        try:
            A, B, emits, nacks = run_both(self._burst_traffic())
        finally:
            TpuSequencerLambda._build_merge = orig
        assert seen["run"], "no INSERT_RUN slot staged for a typing burst"
        assert_equivalent(A, B, emits, nacks,
                          [(f"d{d}", "s", "t") for d in range(2)])

    def test_nacked_member_mid_run_rolls_back(self):
        """A duplicate csn INSIDE a packed run gets nacked by ticketing:
        the mispredicted slot must void, the lane must roll back, and the
        scalar re-run must land the admitted members — fast == object."""
        doc = "d0"
        msgs = [_join("c0")]
        pos = 0
        for i in range(1, 13):
            dup = 6 if i == 7 else i  # csn 6 repeats mid-burst
            text = chr(96 + i) * 2
            msgs.append(DocumentMessage(
                client_sequence_number=dup,
                reference_sequence_number=0,
                type=MessageType.OPERATION,
                contents={"address": "s", "contents": {
                    "address": "t", "contents": {
                        "type": OP_INSERT, "pos1": pos,
                        "seg": {"text": text}}}}))
            pos += len(text)
        A, B, emits, nacks = run_both([(doc, Boxcar("t", doc, "c0",
                                                    msgs))])
        assert_equivalent(A, B, emits, nacks, [(doc, "s", "t")])


class TestFusedRunsServeConformance:
    def test_fused_runs_window_matches_scan(self, monkeypatch):
        """serve_megakernel with fused=True AND run-packed bursts: the Mosaic
        INSERT_RUN variant path (interpret mode on CPU) must match the
        scan+runs path message-for-message and byte-for-byte."""
        import functools

        import jax

        from fluidframework_tpu.mergetree import pallas_apply

        if jax.default_backend() != "tpu":
            monkeypatch.setattr(
                pallas_apply, "apply_ops_fused_pallas",
                functools.partial(pallas_apply.apply_ops_fused_pallas,
                                  interpret=True))

        def burst(doc, cid, k=11, prepend=False):
            msgs = [_join(cid)]
            pos = 0
            for i in range(1, k + 1):
                text = chr(96 + i)
                msgs.append(DocumentMessage(
                    client_sequence_number=i,
                    reference_sequence_number=0,
                    type=MessageType.OPERATION,
                    contents={"address": "s", "contents": {
                        "address": "t", "contents": {
                            "type": OP_INSERT, "pos1": pos,
                            "seg": {"text": text}}}}))
                if not prepend:
                    pos += len(text)
            return (doc, Boxcar("t", doc, cid, msgs))

        traffic = [burst("d0", "c0"), burst("d1", "c1", prepend=True)]
        ea, na, eb, nb = [], [], [], []
        A = _lam(lambda d, m: ea.append(_emit_key(d, m)),
                 lambda d, c, n: na.append((d, c, n.content.code)))
        B = _lam(lambda d, m: eb.append(_emit_key(d, m)),
                 lambda d, c, n: nb.append((d, c, n.content.code)))
        A._fused_serve = False   # scan + runs
        B._fused_serve = True    # fused runs variant + runs
        for i, (doc, box) in enumerate(traffic):
            A.handler_raw(_qm(i, doc, box, raw=True))
            B.handler_raw(_qm(i, doc, box, raw=True))
        A.flush()
        B.flush()
        A.drain()
        B.drain()
        assert_equivalent(A, B, (ea, eb), (na, nb),
                          [("d0", "s", "t"), ("d1", "s", "t")])


class TestFusedDegrade:
    def test_lowering_failure_degrades_in_policy_order(self, monkeypatch):
        """A fused-path failure at a production shape degrades without
        data loss: the megakernel retries the same ring with the scan
        op-phase (counted serving.megakernel_fallbacks), the fused mode
        stays off from then on, and run packing — which the scan
        op-phase serves too — stays on; same results as the object
        oracle."""
        from fluidframework_tpu.mergetree import pallas_apply
        from fluidframework_tpu.server import serve_step
        from fluidframework_tpu.telemetry import counters

        def boom(*a, **k):
            raise RuntimeError("mosaic says no")

        monkeypatch.setattr(pallas_apply, "apply_ops_fused_pallas", boom)
        # Earlier tests may have CACHED fused traces for these shapes —
        # a cache hit would skip tracing and never call the patched
        # function, making this test order-dependent.
        for fn in (serve_step.serve_megakernel,
                   serve_step.serve_megakernel_keep):
            if hasattr(fn, "clear_cache"):
                fn.clear_cache()
        fallbacks = counters.get("serving.megakernel_fallbacks")

        def burst(doc, cid, k=10):
            msgs = [_join(cid)]
            pos = 0
            for i in range(1, k + 1):
                msgs.append(DocumentMessage(
                    client_sequence_number=i,
                    reference_sequence_number=0,
                    type=MessageType.OPERATION,
                    contents={"address": "s", "contents": {
                        "address": "t", "contents": {
                            "type": OP_INSERT, "pos1": pos,
                            "seg": {"text": chr(96 + i)}}}}))
                pos += 1
            return (doc, Boxcar("t", doc, cid, msgs))

        ea, na, eb, nb = [], [], [], []
        A = _lam(lambda d, m: ea.append(_emit_key(d, m)),
                 lambda d, c, n: na.append((d, c, n.content.code)))
        B = _lam(lambda d, m: eb.append(_emit_key(d, m)),
                 lambda d, c, n: nb.append((d, c, n.content.code)))
        A._fused_serve = False
        B._fused_serve = True  # forces the degrade cascade
        for i, (doc, box) in enumerate([burst("d0", "c0")]):
            A.handler_raw(_qm(i, doc, box, raw=True))
            B.handler_raw(_qm(i, doc, box, raw=True))
        A.flush()
        B.flush()
        A.drain()
        B.drain()
        assert counters.get("serving.megakernel_fallbacks") - \
            fallbacks == 1
        assert B._fused_serve is False, "fused forfeits, sticky"
        assert B.pack_runs is True, "the scan op-phase keeps packing"
        assert_equivalent(A, B, (ea, eb), (na, nb), [("d0", "s", "t")])
