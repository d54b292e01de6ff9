"""Full-stack E2E: Loader/Container <-> LocalServer running the real
lambda pipeline (reference end-to-end-tests over LocalDeltaConnectionServer,
SURVEY.md §4.4)."""

import pytest

from fluidframework_tpu.server.local_server import LocalServer
from fluidframework_tpu.loader.drivers.local import LocalDocumentServiceFactory
from fluidframework_tpu.loader.container import Container, Loader
from fluidframework_tpu.dds.map import SharedMap
from fluidframework_tpu.dds.sequence import SharedString
from fluidframework_tpu.dds.counter import SharedCounter


def make_doc(server, doc_id="doc"):
    """Create-detached -> populate -> attach (the reference detached-attach
    flow), returning (loader, container, datastore)."""
    loader = Loader(LocalDocumentServiceFactory(server))
    container = loader.create_detached(doc_id)
    ds = container.runtime.create_datastore("default")
    return loader, container, ds


class TestCreateAttachLoad:
    def test_attach_then_load_second_client(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        text = ds1.create_channel("text", SharedString.TYPE)
        text.insert_text(0, "made offline")
        c1.attach()
        assert c1.connected

        c2 = loader.resolve("doc")
        t2 = c2.runtime.get_datastore("default").get_channel("text")
        assert t2.get_text() == "made offline"

        # Live collaboration after load.
        t2.insert_text(0, "c2:")
        text.insert_text(text.get_length(), "!")
        assert text.get_text() == t2.get_text() == "c2:made offline!"

    def test_three_clients_counter(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        ds1.create_channel("clicks", SharedCounter.TYPE)
        c1.attach()
        c2 = loader.resolve("doc")
        c3 = loader.resolve("doc")
        counters = [
            c.runtime.get_datastore("default").get_channel("clicks")
            for c in (c1, c2, c3)]
        for i, counter in enumerate(counters):
            counter.increment(i + 1)
        assert [c.value for c in counters] == [6, 6, 6]

    def test_audience_tracks_members(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        c1.attach()
        c2 = loader.resolve("doc")
        assert len(c1.audience.members) == 2
        c2.close()
        server.pump()
        assert len(c1.audience.members) == 1


class TestSummarizeFlow:
    def test_client_summarize_scribe_ack(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        m = ds1.create_channel("root", SharedMap.TYPE)
        c1.attach()
        m.set("k", "v")
        results = []
        c1.summarize(lambda handle, ack, contents:
                     results.append((handle, ack)))
        server.pump()
        assert results and results[0][1] is True

        # A late client loads from the new summary without replaying ops
        # it covers (op tail may be empty).
        c2 = loader.resolve("doc")
        m2 = c2.runtime.get_datastore("default").get_channel("root")
        assert m2.get("k") == "v"

    def test_bad_summary_handle_nacked(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        c1.attach()
        results = []
        from fluidframework_tpu.protocol.messages import MessageType
        c1.delta_manager.submit(
            MessageType.SUMMARIZE, {"handle": "deadbeef"},
            before_send=lambda csn: c1._summary_waiters.append(
                {"csn": csn, "summary_seq": None,
                 "fn": lambda handle, ack, contents: results.append(ack)}))
        server.pump()
        assert results == [False]

    def test_incremental_summary_dedupes_blobs(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        m = ds1.create_channel("root", SharedMap.TYPE)
        c1.attach()
        m.set("k", 1)
        h1 = c1.summarize()
        server.pump()
        m.set("k", 2)
        h2 = c1.summarize()
        server.pump()
        assert h1 != h2
        store = server.storage("doc")
        assert store.get_ref("main") == h2


class TestReconnect:
    def test_nack_triggers_resubmit(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        m = ds1.create_channel("root", SharedMap.TYPE)
        c1.attach()
        c2 = loader.resolve("doc")
        m2 = c2.runtime.get_datastore("default").get_channel("root")

        # Force a stale submission: disconnect c1's socket server-side, then
        # submit — the op is lost; reconnect resubmits.
        c1.delta_manager.connection._conn.connected = False
        try:
            m.set("lost", "no")
        except ConnectionError:
            pass
        c1.reconnect()
        server.pump()
        assert m2.get("lost") == "no"
        assert m.get("lost") == "no"

    def test_explicit_reconnect_with_pending_string_ops(self):
        server = LocalServer(auto_pump=True)
        loader, c1, ds1 = make_doc(server)
        s1 = ds1.create_channel("text", SharedString.TYPE)
        c1.attach()
        c2 = loader.resolve("doc")
        s2 = c2.runtime.get_datastore("default").get_channel("text")
        s1.insert_text(0, "hello")
        # Concurrent edit from c2, then c1 reconnects (new identity).
        s2.insert_text(0, "x")
        c1.reconnect()
        server.pump()
        assert s1.get_text() == s2.get_text()


class TestServerInternals:
    def test_scriptorium_idempotent_on_replay(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        m = ds1.create_channel("root", SharedMap.TYPE)
        c1.attach()
        m.set("a", 1)
        n1 = len(server.deltas)
        # Simulate a crashed scriptorium replaying from offset 0.
        for key in list(server.log.checkpoints):
            if key[0] == "scriptorium":
                del server.log.checkpoints[key]
        server.pump()
        assert len(server.deltas) == n1  # dup inserts ignored

    def test_deli_nacks_unjoined_client(self):
        from fluidframework_tpu.protocol.messages import (
            Boxcar, DocumentMessage, MessageType)
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        c1.attach()
        nacks = []
        conn = server.connect("doc")
        conn.on("nack", nacks.append)
        # Forge a message from a never-joined client id.
        server._submit_boxcar(Boxcar(
            tenant_id="local", document_id="doc", client_id="ghost",
            contents=[DocumentMessage(client_sequence_number=1,
                                      reference_sequence_number=0,
                                      type=MessageType.OPERATION,
                                      contents={})]))
        server.pump()
        # Ghost has no connection; no crash, and no sequenced op appeared.
        ops = server.get_deltas("doc")
        assert all(o["client_id"] != "ghost" for o in ops)

    def test_deli_checkpoint_persisted(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        m = ds1.create_channel("root", SharedMap.TYPE)
        c1.attach()
        m.set("a", 1)
        assert server.sequence_number("doc") >= 2  # join + op

    def test_copier_captures_raw_ops(self):
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        m = ds1.create_channel("root", SharedMap.TYPE)
        c1.attach()
        m.set("a", 1)
        assert len(server.raw_deltas) >= 2


class TestOpSizeCeiling:
    """Server-side max-op-size enforcement (reference alfred
    maxMessageSize): oversized content nacks 413 on BOTH sequencer paths;
    well-behaved clients chunk long before the ceiling."""

    def _giant_and_ok(self, server):
        from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                          MessageType,
                                                          NACK_TOO_LARGE)
        conn = server.connect("doc")
        nacks = []
        conn.on("nack", nacks.append)
        seq_before = server.sequence_number("doc")
        conn.submit([DocumentMessage(
            client_sequence_number=1, reference_sequence_number=0,
            type=MessageType.OPERATION,
            contents={"blob": "x" * (2 * 1024 * 1024)})])
        assert nacks and nacks[-1].content.code == NACK_TOO_LARGE
        assert server.sequence_number("doc") == seq_before
        # A normal op still sequences afterwards.
        conn.submit([DocumentMessage(
            client_sequence_number=2, reference_sequence_number=0,
            type=MessageType.OPERATION, contents={"ok": 1})])
        assert server.sequence_number("doc") == seq_before + 1

    def test_scalar_deli_nacks_oversized(self):
        self._giant_and_ok(LocalServer())

    def test_tpu_sequencer_nacks_oversized(self):
        from fluidframework_tpu.server.local_server import TpuLocalServer
        self._giant_and_ok(TpuLocalServer())

    def test_chunked_large_op_still_round_trips(self):
        """The client chunking path keeps every wire message under the
        ceiling, so app-level ops far above 1MB still work end-to-end."""
        server = LocalServer()
        loader, c1, ds1 = make_doc(server)
        m1 = ds1.create_channel("map", SharedMap.TYPE)
        c1.attach()
        c2 = loader.resolve("doc")
        big = "y" * (3 * 1024 * 1024)
        m1.set("big", big)
        m2 = c2.runtime.get_datastore("default").get_channel("map")
        assert m2.get("big") == big


class TestThrottling:
    """Per-connection op-rate limiting (reference alfred throttler):
    429 nacks with retryAfter; clients back off and converge."""

    def _server(self, rate, burst):
        from fluidframework_tpu.core.config import ConfigProvider
        cfg = ConfigProvider({"alfred": {"throttling": {
            "opsPerSecond": rate, "burst": burst}}})
        return LocalServer(config=cfg)

    def test_burst_exceeded_nacks_429_with_retry_after(self):
        from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                          MessageType,
                                                          NACK_THROTTLED)
        server = self._server(rate=5, burst=3)
        conn = server.connect("doc")
        nacks = []
        conn.on("nack", nacks.append)
        for i in range(6):
            conn.submit([DocumentMessage(
                client_sequence_number=i + 1,
                reference_sequence_number=0,
                type=MessageType.OPERATION, contents={"i": i})])
        assert nacks, "burst of 6 over burst=3 must throttle"
        assert nacks[0].content.code == NACK_THROTTLED
        assert nacks[0].content.retry_after_s > 0
        # Admitted ops sequenced; throttled ones did not.
        assert 0 < server.sequence_number("doc") - 1 < 6  # -1: the join

    def test_bucket_refills_over_time(self):
        import time as _time
        server = self._server(rate=50, burst=2)
        conn = server.connect("doc")
        nacks = []
        conn.on("nack", nacks.append)
        from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                          MessageType)

        def push(i):
            conn.submit([DocumentMessage(
                client_sequence_number=i, reference_sequence_number=0,
                type=MessageType.OPERATION, contents={})])
        push(1)
        push(2)
        push(3)  # bucket empty: throttled
        assert len(nacks) == 1
        _time.sleep(0.1)  # 50/s refill: ~5 tokens
        push(4)
        assert len(nacks) == 1  # admitted after refill

    def test_container_backs_off_and_converges(self):
        import time as _time
        server = self._server(rate=200, burst=5)
        loader, c1, ds1 = make_doc(server)
        m1 = ds1.create_channel("map", SharedMap.TYPE)
        c1.attach()
        for i in range(12):  # exceeds burst: nack -> retryAfter -> resubmit
            m1.set(f"k{i}", i)
        # Throttle recovery waits retryAfter on a worker thread, then
        # reconnects + resubmits: wait for convergence.
        want = {f"k{i}": i for i in range(12)}
        deadline = _time.time() + 20
        while _time.time() < deadline and dict(m1.items()) != want:
            _time.sleep(0.05)
        c2 = loader.resolve("doc")
        m2 = c2.runtime.get_datastore("default").get_channel("map")
        deadline = _time.time() + 20
        while _time.time() < deadline and dict(m2.items()) != want:
            _time.sleep(0.05)
        assert dict(m2.items()) == want

    def test_load_during_recovery_resubmit_does_not_deadlock(self):
        """The interleaving behind a lock-order deadlock, forced: c1's
        throttle-recovery thread resubmits under c1's DeltaManager.lock
        (-> server pump) while a late loader's connect is inside the
        server pump delivering its join to c1 (-> c1's lock). Both must
        finish and converge. Every wait is bounded."""
        import threading
        import time as _time
        server = self._server(rate=200, burst=5)
        loader, c1, ds1 = make_doc(server)
        m1 = ds1.create_channel("map", SharedMap.TYPE)
        c1.attach()

        resubmitting = threading.Event()
        delivering = threading.Event()
        held = []
        resubmit_all = c1.runtime._resubmit_all

        def held_resubmit():
            # Recovery thread, holding c1's lock: wait until the loader's
            # pump is delivering into c1, then resubmit.
            if not held and threading.current_thread() \
                    is not threading.main_thread():
                held.append(True)
                resubmitting.set()
                delivering.wait(10)
            resubmit_all()

        enqueue = c1.delta_manager._enqueue

        def watched_enqueue(msg):
            if threading.current_thread().name == "late-loader":
                delivering.set()
            enqueue(msg)

        c1.runtime._resubmit_all = held_resubmit
        c1.delta_manager._enqueue = watched_enqueue
        # Under c1's lock: the recovery thread's reconnect waits until
        # every edit is parked, so its resubmit is the one held above.
        with c1.op_lock:
            for i in range(12):  # exceeds burst: 429 -> recovery thread
                m1.set(f"k{i}", i)
        assert resubmitting.wait(10), "throttle recovery never resubmitted"

        loaded = {}

        def load():
            loaded["c2"] = loader.resolve("doc")

        late = threading.Thread(target=load, name="late-loader",
                                daemon=True)
        late.start()
        late.join(timeout=10)
        assert not late.is_alive(), \
            "late loader deadlocked against the resubmitting container"
        assert delivering.is_set(), "the interleaving was not forced"

        want = {f"k{i}": i for i in range(12)}
        m2 = loaded["c2"].runtime.get_datastore("default").get_channel("map")
        deadline = _time.time() + 20
        while _time.time() < deadline and not (
                dict(m1.items()) == dict(m2.items()) == want):
            _time.sleep(0.05)
        assert dict(m1.items()) == want
        assert dict(m2.items()) == want

    def test_per_document_bucket_survives_reconnect(self):
        """Reconnecting must not mint a fresh throttle budget (the bucket
        is keyed by document on the server)."""
        from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                          MessageType,
                                                          NACK_THROTTLED)
        server = self._server(rate=1, burst=3)
        conn = server.connect("doc")
        nacks = []
        conn.on("nack", nacks.append)
        for i in range(3):
            conn.submit([DocumentMessage(
                client_sequence_number=i + 1,
                reference_sequence_number=0,
                type=MessageType.OPERATION, contents={})])
        conn.disconnect()
        conn2 = server.connect("doc")  # same doc: same (drained) bucket
        nacks2 = []
        conn2.on("nack", nacks2.append)
        conn2.submit([DocumentMessage(
            client_sequence_number=1, reference_sequence_number=0,
            type=MessageType.OPERATION, contents={})])
        assert nacks2 and nacks2[0].content.code == NACK_THROTTLED


class TestOversizedNonRetryable:
    def test_unchunkable_oversized_op_closes_container(self):
        """A 413 is non-retryable: the container surfaces an error and
        closes instead of reconnect-looping with the identical op."""
        loader, c1, ds1 = make_doc(LocalServer())
        m1 = ds1.create_channel("map", SharedMap.TYPE)
        c1.attach()
        c1.runtime.max_op_size = 8 * 1024 * 1024  # defeat client chunking
        errors = []
        c1.on("error", errors.append)
        m1.set("too-big", "x" * (2 * 1024 * 1024))
        assert errors and errors[0].content.code == 413
        assert c1.closed
