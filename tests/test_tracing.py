"""telemetry/tracing.py: span/context semantics, sampling policy, the
flight recorder, wire propagation, and the end-to-end op trace through
the real pipeline (submit -> ticket -> flush -> broadcast)."""

import json
import threading

import pytest

from fluidframework_tpu.telemetry import counters, tracing


@pytest.fixture(autouse=True)
def _clean():
    counters.reset()
    tracing.reset()
    yield
    counters.reset()
    tracing.reset()


class TestSpanBasics:
    def test_disabled_is_noop(self):
        assert not tracing.enabled()
        with tracing.span("x", root=True):
            pass
        assert len(tracing.recorder) == 0

    def test_root_span_records_when_sampled(self):
        tracing.configure(sample=1)
        with tracing.span("stage", root=True, detail=7):
            pass
        spans = tracing.recorder.snapshot()
        assert [s["name"] for s in spans] == ["stage"]
        assert spans[0]["attrs"]["detail"] == 7
        assert spans[0]["parent_id"] is None

    def test_nesting_inherits_trace_and_parent(self):
        tracing.configure(sample=1)
        with tracing.span("outer", root=True) as outer:
            with tracing.span("inner"):
                pass
        inner, outer_rec = tracing.recorder.snapshot()
        assert inner["name"] == "inner"
        assert inner["trace_id"] == outer_rec["trace_id"]
        assert inner["parent_id"] == outer.ctx.span_id

    def test_non_root_without_parent_is_silent(self):
        tracing.configure(sample=1)
        with tracing.span("orphan"):
            pass
        assert len(tracing.recorder) == 0

    def test_exception_records_error_span(self):
        tracing.configure(sample=1)
        with pytest.raises(RuntimeError):
            with tracing.span("bad", root=True):
                raise RuntimeError("boom")
        (span,) = tracing.recorder.snapshot()
        assert span["attrs"].get("error") is True

    def test_explicit_end_is_idempotent(self):
        tracing.configure(sample=1)
        sp = tracing.span("once", root=True)
        sp.end()
        sp.end()
        assert len(tracing.recorder) == 1

    def test_hist_feeds_histogram_even_when_disabled(self):
        with tracing.span("s", hist="stage.x"):
            pass
        snap = counters.latency_snapshot()
        assert snap["stage.x"]["count"] == 1
        assert len(tracing.recorder) == 0


class TestSampling:
    def test_one_in_n(self):
        tracing.configure(sample=4)
        roots = [tracing.new_op_trace() for _ in range(16)]
        minted = [r for r in roots if r is not None]
        assert len(minted) == 4

    def test_always_sample_on_slow(self):
        tracing.configure(sample=1000, slow_ms=0.0)
        # Deterministically unsampled context; slow_ms=0 means every
        # span crosses the slow threshold at end().
        ctx = tracing.TraceContext("f" * 16, "1", sampled=False)
        with tracing.span("slowpoke", parent=ctx):
            pass
        spans = tracing.recorder.snapshot()
        assert [s["name"] for s in spans] == ["slowpoke"]
        assert spans[0]["sampled"] is False  # recorded BECAUSE slow

    def test_fast_unsampled_not_recorded(self):
        tracing.configure(sample=1000, slow_ms=10_000.0)
        ctx = tracing.TraceContext("f" * 16, "1", sampled=False)
        with tracing.span("fast", parent=ctx):
            pass
        assert len(tracing.recorder) == 0


class TestFlightRecorder:
    def test_bounded_overwrites_oldest(self):
        tracing.configure(sample=1, capacity=4)
        for i in range(7):
            with tracing.span(f"s{i}", root=True):
                pass
        names = [s["name"] for s in tracing.recorder.snapshot()]
        assert len(names) == 4
        assert names == ["s3", "s4", "s5", "s6"]  # oldest first
        assert tracing.recorder.dropped == 3

    def test_drain_clears(self):
        tracing.configure(sample=1)
        with tracing.span("a", root=True):
            pass
        assert len(tracing.recorder.drain()) == 1
        assert tracing.recorder.drain() == []

    def test_concurrent_records(self):
        tracing.configure(sample=1, capacity=4096)

        def work():
            for _ in range(100):
                with tracing.span("t", root=True):
                    pass

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(tracing.recorder) == 400


class TestWirePropagation:
    def test_stamp_and_extract(self):
        from fluidframework_tpu.protocol.messages import DocumentMessage
        tracing.configure(sample=1)
        ctx = tracing.TraceContext("t" * 16, "1", sampled=True)
        msg = DocumentMessage(client_sequence_number=1,
                              reference_sequence_number=0, type="op")
        tracing.stamp_message(msg, ctx)
        # Compact string form: asdict-atomic on the persistence path.
        assert msg.metadata == {"trace": f"{'t' * 16}:1:1"}
        back = tracing.message_context(msg)
        assert back.trace_id == ctx.trace_id
        assert back.span_id == "1"
        assert back.sampled is True

    def test_unsampled_wire_round_trip(self):
        tracing.configure(sample=1)
        ctx = tracing.TraceContext("abc", "9", sampled=False)
        back = tracing.TraceContext.from_wire(ctx.to_wire())
        assert back.sampled is False and back.trace_id == "abc"

    def test_legacy_dict_form_still_parses(self):
        tracing.configure(sample=1)
        back = tracing.TraceContext.from_wire(
            {"traceId": "x", "spanId": "2", "sampled": False})
        assert back.trace_id == "x" and back.sampled is False

    def test_stamp_preserves_existing_metadata(self):
        from fluidframework_tpu.protocol.messages import DocumentMessage
        tracing.configure(sample=1)
        msg = DocumentMessage(client_sequence_number=1,
                              reference_sequence_number=0, type="op",
                              metadata={"batch": True})
        tracing.stamp_message(msg, tracing.TraceContext("a", "b"))
        assert msg.metadata["batch"] is True
        assert tracing.message_context(msg).trace_id == "a"

    def test_context_survives_json_round_trip(self):
        from fluidframework_tpu.protocol.messages import DocumentMessage
        from fluidframework_tpu.server.wire import (
            document_message_from_dict, document_message_to_dict)
        tracing.configure(sample=1)
        msg = DocumentMessage(client_sequence_number=1,
                              reference_sequence_number=0, type="op")
        tracing.stamp_message(msg, tracing.TraceContext("deadbeef", "7"))
        wire = json.loads(json.dumps(document_message_to_dict(msg)))
        back = tracing.message_context(document_message_from_dict(wire))
        assert back is not None and back.trace_id == "deadbeef"

    def test_op_trace_handoff(self):
        tracing.configure(sample=1)
        ctx = tracing.new_op_trace()
        assert ctx is not None
        assert tracing.take_op_trace() is ctx
        assert tracing.take_op_trace() is None

    def test_unsampled_edit_decision_respected_at_submit(self):
        # One sampler draw per op: an edit whose draw said "no" must not
        # get a second roll at the driver boundary (that would double
        # the effective rate and mint traces missing client.local_edit).
        tracing.configure(sample=2)
        minted = 0
        for _ in range(20):
            edit_ctx = tracing.new_op_trace()
            submit_ctx = tracing.ensure_op_context()
            assert (edit_ctx is None) == (submit_ctx is None)
            if submit_ctx is not None:
                assert submit_ctx is edit_ctx
                minted += 1
        assert minted == 10  # exactly 1-in-2, not 1-in-2 twice-rolled


class TestChromeExport:
    def test_events_shape(self):
        tracing.configure(sample=1)
        with tracing.span("parent", root=True):
            with tracing.span("child"):
                pass
        out = tracing.chrome_trace()
        assert json.dumps(out)  # serializable
        assert out["displayTimeUnit"] == "ms"
        by_name = {e["name"]: e for e in out["traceEvents"]}
        assert set(by_name) == {"parent", "child"}
        import os
        for e in out["traceEvents"]:
            assert e["ph"] == "X" and e["pid"] == os.getpid()
            assert e["args"]["proc"] == tracing.process_name()
            assert e["dur"] >= 0
        assert (by_name["child"]["args"]["parent_id"]
                == by_name["parent"]["args"]["span_id"])


class TestClientEditRoots:
    def test_local_edit_mints_trace_and_parks_context(self):
        from fluidframework_tpu.mergetree.client import MergeTreeClient
        tracing.configure(sample=1)
        client = MergeTreeClient(client_id=0)
        client.insert_text_local(0, "hello")
        spans = tracing.recorder.snapshot()
        assert [s["name"] for s in spans] == ["client.local_edit"]
        parked = tracing.take_op_trace()
        assert parked is not None
        assert parked.trace_id == spans[0]["trace_id"]

    def test_edits_untraced_when_disabled(self):
        from fluidframework_tpu.mergetree.client import MergeTreeClient
        client = MergeTreeClient(client_id=0)
        client.insert_text_local(0, "hello")
        assert len(tracing.recorder) == 0
        assert tracing.take_op_trace() is None


SERVING_SUBSPANS = {"serving.pack", "serving.dispatch", "serving.readback",
                    "serving.fold_rescue", "serving.gc"}


class TestEndToEndPipeline:
    """A single traced op yields one parent trace spanning
    submit -> ticket -> flush -> broadcast, with the named serving
    sub-spans riding the same trace on the device-batched path."""

    def _drive(self, server):
        from fluidframework_tpu.loader.drivers.local import (
            LocalDocumentServiceFactory)
        from fluidframework_tpu.mergetree.client import OP_INSERT
        from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                          MessageType)
        svc = LocalDocumentServiceFactory(server) \
            .create_document_service("doc-e2e")
        conn = svc.connect_to_delta_stream({"user": "u"})
        seen = []
        conn.on("op", seen.append)
        conn.submit([DocumentMessage(
            client_sequence_number=1, reference_sequence_number=0,
            type=MessageType.OPERATION,
            contents={"address": "s", "contents": {
                "address": "t", "contents": {
                    "type": OP_INSERT, "pos1": 0,
                    "seg": {"text": "traced"}}}})])
        assert seen, "op was not sequenced/broadcast"
        by_trace = {}
        for s in tracing.recorder.snapshot():
            by_trace.setdefault(s["trace_id"], set()).add(s["name"])
        return by_trace

    def test_scalar_pipeline_full_trace(self):
        from fluidframework_tpu.server.local_server import LocalServer
        tracing.configure(sample=1)
        by_trace = self._drive(LocalServer())
        assert any({"driver.submit", "server.ingest", "deli.ticket",
                    "broadcaster.fanout"} <= names
                   for names in by_trace.values())

    def test_tpu_pipeline_full_trace_with_serving_subspans(self):
        from fluidframework_tpu.server.local_server import TpuLocalServer
        tracing.configure(sample=1)
        by_trace = self._drive(TpuLocalServer())
        want = ({"driver.submit", "server.ingest", "deli.ticket",
                 "serving.flush", "broadcaster.fanout"}
                | SERVING_SUBSPANS)
        full = [names for names in by_trace.values() if want <= names]
        assert full, {t: sorted(n) for t, n in by_trace.items()}

    def test_stage_histograms_fill_without_tracing(self):
        from fluidframework_tpu.server.local_server import TpuLocalServer
        assert not tracing.enabled()
        self_spans_before = len(tracing.recorder)
        from fluidframework_tpu.loader.drivers.local import (
            LocalDocumentServiceFactory)
        from fluidframework_tpu.mergetree.client import OP_INSERT
        from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                          MessageType)
        server = TpuLocalServer()
        svc = LocalDocumentServiceFactory(server) \
            .create_document_service("doc-h")
        conn = svc.connect_to_delta_stream({"user": "u"})
        conn.submit([DocumentMessage(
            client_sequence_number=1, reference_sequence_number=0,
            type=MessageType.OPERATION,
            contents={"address": "s", "contents": {
                "address": "t", "contents": {
                    "type": OP_INSERT, "pos1": 0,
                    "seg": {"text": "x"}}}})])
        snap = counters.latency_snapshot()
        assert "serving.flush" in snap
        assert SERVING_SUBSPANS <= set(snap)
        assert len(tracing.recorder) == self_spans_before  # no spans


class TestOffPath:
    def test_off_returns_the_shared_no_op(self):
        assert not tracing.enabled()
        assert tracing.span("x", root=True) is tracing.NULL_SPAN
        assert tracing.span("x", parent=tracing.TraceContext("t", "s")) \
            is tracing.NULL_SPAN
        assert isinstance(tracing.span("x", root=True, hist="h"),
                          tracing._HistTimer)
        assert tracing._annotation is None

    def test_telemetry_imports_no_jax(self):
        import subprocess
        import sys

        code = ("import sys\n"
                "import fluidframework_tpu.telemetry\n"
                "from fluidframework_tpu.telemetry import tracing\n"
                "tracing.configure(sample=1)\n"
                "with tracing.span('x', root=True):\n"
                "    pass\n"
                "print(sorted(m for m in sys.modules\n"
                "             if m == 'jax' or m.startswith('jax.')))\n")
        out = subprocess.run([sys.executable, "-c", code], check=True,
                             capture_output=True, text=True, timeout=120)
        assert out.stdout.strip() == "[]"


def _host_events(path):
    """{event name} on the host planes of the trace under ``path``."""
    import glob
    import os

    import jax

    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert files
    profile = jax.profiler.ProfileData.from_file(files[0])
    return {e.name for plane in profile.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


class TestProfilerBridge:
    def test_spans_land_on_the_profilers_host_line(self, tmp_path):
        import jax

        tracing.configure(sample=1, profiler=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with tracing.span("bridge.outer", root=True):
                with tracing.span("bridge.inner"):
                    pass
            ended = tracing.span("bridge.ended", root=True)
            ended.end()
        finally:
            jax.profiler.stop_trace()
        names = _host_events(str(tmp_path))
        assert {"fluid.bridge.outer", "fluid.bridge.inner"} <= names
        # A span ended by end() is not annotated after the fact.
        assert "fluid.bridge.ended" not in names
        assert {s["name"] for s in tracing.recorder.snapshot()} == {
            "bridge.outer", "bridge.inner", "bridge.ended"}

    def test_bridge_turns_off(self):
        tracing.configure(sample=1, profiler=True)
        assert tracing._annotation is not None
        tracing.configure(profiler=False)
        assert tracing._annotation is None
        with tracing.span("x", root=True) as sp:
            assert sp._note is None


class TestReadPathTrace:
    def _doc(self):
        from fluidframework_tpu.dds.sequence import SharedString
        from fluidframework_tpu.loader.container import Loader
        from fluidframework_tpu.loader.drivers.local import (
            LocalDocumentServiceFactory)
        from fluidframework_tpu.server.local_server import TpuLocalServer

        server = TpuLocalServer()
        loader = Loader(LocalDocumentServiceFactory(server))
        writer = loader.create_detached("doc")
        text = writer.runtime.create_datastore("s").create_channel(
            "t", SharedString.TYPE)
        text.insert_text(0, "base")
        writer.attach()
        for i in range(40):
            text.insert_text(text.get_length(), f"<{i}>")
        server.pump()
        return loader, text.get_text()

    def test_resolve_is_one_trace_with_its_steps(self):
        loader, want = self._doc()
        tracing.configure(sample=1)
        tracing.recorder.drain()
        late = loader.resolve("doc")
        assert late.runtime.get_datastore("s").get_channel("t") \
            .get_text() == want
        spans = tracing.recorder.drain()
        roots = [s for s in spans if s["name"] == "load.resolve"]
        assert len(roots) == 1 and roots[0]["parent_id"] is None
        root = roots[0]
        trace = [s for s in spans if s["trace_id"] == root["trace_id"]]
        by_id = {s["span_id"]: s for s in trace}
        children = {s["name"] for s in trace
                    if s["parent_id"] == root["span_id"]}
        assert {"load.fetch", "load.summary", "load.adopt",
                "load.connect"} <= children
        named = {s["name"]: s for s in trace}
        refresh, extract = named["catchup.refresh"], named["catchup.extract"]
        assert by_id[refresh["parent_id"]]["name"] == "load.fetch"
        assert extract["parent_id"] == refresh["span_id"]
        assert by_id[named["catchup.pack"]["parent_id"]] is refresh
        assert {"catchup.get", "catchup.publish",
                "server.get_deltas"} <= set(named)
        assert named["load.adopt"]["attrs"]["adopted"] is True
        assert named["load.adopt"]["attrs"]["entries"] > 0
        assert named["load.fetch"]["attrs"]["artifact"] is True
        assert named["load.connect"]["attrs"]["residue_ops"] >= 1
        # The late client's first read realizes its body: a root.
        assert any(s["name"] == "sequence.realize"
                   and s["parent_id"] is None for s in spans)

    def test_load_histogram_fills_with_tracing_off(self):
        loader, _ = self._doc()
        loader.resolve("doc")
        assert "load.resolve" in counters.latency_snapshot()
        assert len(tracing.recorder) == 0


class TestCompileSpan:
    def test_a_compile_inside_a_span_is_its_child(self):
        import jax
        import jax.numpy as jnp

        from fluidframework_tpu.telemetry.compile_ledger import \
            install_jax_listener

        assert install_jax_listener()
        tracing.configure(sample=1)
        before = counters.get("compile.backend")
        with tracing.span("stage", root=True):
            parent = tracing.current()
            # A function of its own: its first call compiles (or loads).
            jax.jit(lambda x: x * 7 - 3)(jnp.arange(11)).block_until_ready()
        assert counters.get("compile.backend") >= before + 1
        compiles = [s for s in tracing.recorder.snapshot()
                    if s["name"] == "compile"]
        assert compiles
        assert all(s["parent_id"] == parent.span_id
                   and s["trace_id"] == parent.trace_id for s in compiles)
        assert any("lambda" in s["attrs"]["fun_name"] for s in compiles)
        assert all(s["attrs"]["seconds"] >= 0 for s in compiles)

    def test_no_span_outside_a_trace(self):
        import jax
        import jax.numpy as jnp

        from fluidframework_tpu.telemetry.compile_ledger import \
            install_jax_listener

        install_jax_listener()
        tracing.configure(sample=1)
        before = counters.get("compile.backend")
        jax.jit(lambda x: x * 5 + 1)(jnp.arange(13)).block_until_ready()
        assert counters.get("compile.backend") >= before + 1
        assert not [s for s in tracing.recorder.snapshot()
                    if s["name"] == "compile"]

    def test_chip_smoke_reads_compiles_off_the_ledger(self):
        import os
        import sys

        import jax
        import jax.numpy as jnp

        from fluidframework_tpu.telemetry.compile_ledger import \
            install_jax_listener

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import chip_smoke

        install_jax_listener()
        mark = chip_smoke.compile_mark()
        jax.jit(lambda x: x * 9 + 2)(jnp.arange(17)).block_until_ready()
        since = chip_smoke.compile_since(mark)
        assert since["compile_s"] > 0 and since["seconds"] > 0
        assert since["cache_hits"] >= 0 and since["cache_misses"] >= 0
