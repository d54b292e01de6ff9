"""Pipeline-stage overlap in the lambda host (reference kafka-service/
README.md:58-60: process batch N+1 while batch N's DB writes are in
flight): OverlappedLambdaRunner pumps stages concurrently."""

import os
import sys
import threading
import time

import pytest

from fluidframework_tpu.dds.counter import SharedCounter
from fluidframework_tpu.dds.sequence import SharedString
from fluidframework_tpu.loader.container import Loader
from fluidframework_tpu.loader.drivers.local import LocalDocumentServiceFactory
from fluidframework_tpu.protocol.messages import DocumentMessage, MessageType
from fluidframework_tpu.server.lambdas.base import IPartitionLambda
from fluidframework_tpu.server.local_server import LocalServer, TpuLocalServer
from fluidframework_tpu.server.log import MessageLog
from fluidframework_tpu.server.partition import (
    LambdaRunner,
    OverlappedLambdaRunner,
    PartitionManager,
)


class _SlowLambda(IPartitionLambda):
    def __init__(self, context, delay, seen):
        self.context = context
        self.delay = delay
        self.seen = seen

    def handler(self, message):
        time.sleep(self.delay)
        self.seen.append(message.offset)
        self.context.checkpoint(message.offset)


def _build(runner_cls, n_msgs=30, delay=0.004):
    log = MessageLog(default_partitions=1)
    log.topic("work")
    for i in range(n_msgs):
        log.send("work", "k", i)
    runner = runner_cls()
    seen_a, seen_b = [], []
    runner.add(PartitionManager(
        log, "stage-a", "work",
        lambda ctx: _SlowLambda(ctx, delay, seen_a)))
    runner.add(PartitionManager(
        log, "stage-b", "work",
        lambda ctx: _SlowLambda(ctx, delay, seen_b), offload=True))
    return runner, seen_a, seen_b


class TestOverlappedRunner:
    def test_stages_overlap_in_wall_clock(self):
        """Two stages x 30 messages x 4ms: serial ~= sum of stages,
        overlapped ~= max of stages."""
        serial, sa, sb = _build(LambdaRunner)
        t0 = time.perf_counter()
        serial.pump()
        serial_s = time.perf_counter() - t0
        assert len(sa) == len(sb) == 30

        over, oa, ob = _build(OverlappedLambdaRunner)
        t0 = time.perf_counter()
        over.pump()
        over_s = time.perf_counter() - t0
        over.close()
        assert len(oa) == len(ob) == 30
        # Generous margin for CI noise; the structural claim is "clearly
        # better than serialized", not an exact 2x.
        assert over_s < serial_s * 0.85, (over_s, serial_s)

    def test_processing_matches_serial(self):
        serial, sa, sb = _build(LambdaRunner, n_msgs=20, delay=0)
        serial.pump()
        over, oa, ob = _build(OverlappedLambdaRunner, n_msgs=20, delay=0)
        over.pump()
        over.close()
        assert oa == sa and ob == sb  # same per-stage order, all offsets


class TestOverlappedLocalServer:
    def test_e2e_convergence_overlapped(self):
        server = LocalServer(overlapped=True)
        loader = Loader(LocalDocumentServiceFactory(server))
        c1 = loader.create_detached("doc")
        ds = c1.runtime.create_datastore("default")
        c1.attach()
        text = ds.create_channel("text", SharedString.TYPE)
        c2 = loader.resolve("doc")
        t2 = c2.runtime.get_datastore("default").get_channel("text")
        text.insert_text(0, "over")
        t2.insert_text(t2.get_length(), "lap")
        server.pump()
        assert text.get_text() == t2.get_text() == "overlap"

    def test_reentrant_submit_from_listener_does_not_deadlock(self):
        """A client listener that submits an op while the broadcaster stage
        is mid-pump (on a worker thread) must not deadlock the runner."""
        server = LocalServer(overlapped=True)
        loader = Loader(LocalDocumentServiceFactory(server))
        c1 = loader.create_detached("doc")
        ds = c1.runtime.create_datastore("default")
        c1.attach()
        clicks = ds.create_channel("clicks", SharedCounter.TYPE)
        fired = []

        def on_change(*_):
            if not fired:
                fired.append(True)
                clicks.increment(10)  # reentrant submit from the callback

        clicks.on("incremented", on_change)
        clicks.increment(1)
        server.pump()
        server.pump()  # settle any message left at a pump boundary
        assert clicks.value == 11


def _op(csn, n):
    return DocumentMessage(client_sequence_number=csn,
                           reference_sequence_number=0,
                           type=MessageType.OPERATION, contents={"n": n})


def _ops_seen(received):
    # By sequence number, as a DeltaManager reads them: a nested pump may
    # deliver the message in flight at its broadcaster again.
    ops = {m.sequence_number: m.contents["n"] for m in received
           if m.type == MessageType.OPERATION}
    return [ops[seq] for seq in sorted(ops)]


@pytest.mark.parametrize("overlapped", [False, True])
class TestPumpNeverBlocks:
    """LocalServer.pump has one path for both runners: a pump never
    blocks on a pump another thread has in flight."""

    def test_boxcar_from_another_thread_lands_in_the_pump_in_flight(
            self, overlapped):
        server = LocalServer(overlapped=overlapped)
        conn = server.connect("doc")
        received = []
        conn.on("op", received.append)
        quiescent, b_done = threading.Event(), threading.Event()
        seen = {}

        def ticker():
            # Runs in A's pump at quiescence, after its last read of the
            # log and before it releases the pump lock.
            if threading.current_thread().name == "pump-a" \
                    and not quiescent.is_set():
                quiescent.set()
                seen["b_returned"] = b_done.wait(10)
            return 0

        def pump_a():
            server.pump()
            seen["after_a"] = _ops_seen(received)

        server.runner.add_ticker(ticker)
        a = threading.Thread(target=pump_a, name="pump-a", daemon=True)
        a.start()
        assert quiescent.wait(10)
        conn.submit([_op(1, 7)])  # thread B: auto_pump -> pump()
        b_done.set()
        a.join(timeout=20)
        assert not a.is_alive()
        assert seen["b_returned"], "B's pump blocked on A's pump"
        assert seen["after_a"] == [7]  # sequenced + delivered by A's pump

    def test_listener_submitting_during_pump_nests(self, overlapped):
        server = LocalServer(overlapped=overlapped)
        conn = server.connect("doc")
        received, fired = [], []

        def on_op(msg):
            received.append(msg)
            if not fired and _ops_seen(received) == [1]:
                fired.append(True)
                conn.submit([_op(2, 2)])  # re-enters pump() on this thread

        conn.on("op", on_op)
        conn.submit([_op(1, 1)])
        assert _ops_seen(received) == [1, 2]

    def test_concurrent_submitters_strand_nothing(self, overlapped):
        """More submitting threads than cores, switching often: a lost
        _pump_wanted update would leave a boxcar in the log unsequenced
        after every pump() has returned."""
        from fluidframework_tpu.core.config import ConfigProvider
        server = LocalServer(overlapped=overlapped, config=ConfigProvider(
            {"admission": {"enabled": False}}))
        threads_n, ops_n = 2 * (os.cpu_count() or 4), 20
        conns = [server.connect("doc") for _ in range(threads_n)]

        def submit_all(conn):
            for i in range(1, ops_n + 1):
                conn.submit([_op(i, i)])

        threads = [threading.Thread(target=submit_all, args=(c,),
                                    daemon=True) for c in conns]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        # Joins + ops, all sequenced without a further pump.
        assert server.sequence_number("doc") == threads_n * (1 + ops_n)


class TestPumpHeldOffByCatchupRead:
    """refresh_catchup and get_catchup hold the pump lock too, without
    pumping: a pump() that skipped meanwhile runs when they release it."""

    @pytest.mark.parametrize("read", ["get_catchup", "refresh_catchup"])
    def test_boxcar_sequenced_when_the_read_returns(self, read,
                                                    monkeypatch):
        server = TpuLocalServer()
        conn = server.connect("doc")
        received = []
        conn.on("op", received.append)
        inside, b_done = threading.Event(), threading.Event()
        seen = {}
        sequencers = server.ingest.sequencers

        def held_sequencers():
            # Both reads refresh here (no artifact yet), under the lock.
            if threading.current_thread().name == "reader" \
                    and not inside.is_set():
                inside.set()
                seen["b_returned"] = b_done.wait(10)
            return sequencers()

        def reader():
            getattr(server, read)(
                *(["doc"] if read == "get_catchup" else []))
            seen["after_read"] = _ops_seen(received)

        monkeypatch.setattr(server.ingest, "sequencers", held_sequencers)
        a = threading.Thread(target=reader, name="reader", daemon=True)
        a.start()
        assert inside.wait(10)
        conn.submit([_op(1, 7)])  # thread B: auto_pump -> pump() skips
        b_done.set()
        a.join(timeout=20)
        assert not a.is_alive()
        assert seen["b_returned"], "B's pump blocked on the catchup read"
        assert seen["after_read"] == [7]  # no further pump() call

    def test_round_that_raises_leaves_its_work_for_the_next_holder(
            self, monkeypatch):
        server = TpuLocalServer()
        conn = server.connect("doc")
        received = []
        conn.on("op", received.append)
        runner_pump = server.runner.pump

        def failing_pump():
            raise RuntimeError("stage failed")

        monkeypatch.setattr(server.runner, "pump", failing_pump)
        with pytest.raises(RuntimeError, match="stage failed"):
            conn.submit([_op(1, 7)])
        monkeypatch.setattr(server.runner, "pump", runner_pump)
        server.refresh_catchup()  # the next holder pumps on release
        assert _ops_seen(received) == [7]
