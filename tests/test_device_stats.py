"""Device-resident telemetry planes + the compile/dispatch observatory
(docs/observability.md v2, ISSUE 14).

Locks the tentpole's contracts at pytest granularity (the 512-doc gate
is `make obs-smoke`):

  * telemetry on/off BIT-IDENTITY on a contended ragged fleet whose
    pipelined run includes mid-flight overflow recovery — identical
    emit stream, identical lane planes;
  * device-counted op totals reconcile EXACTLY with the host-side
    mirrors (serving windows/bursts AND the paged apply);
  * the extract plane reports zamboni reclamation exactly;
  * compile-ledger warm/cold attribution pinned;
  * the /metrics.prom cardinality guard bounds dynamic label fan-out;
  * the monitor surfaces (/health compileLedger + deviceStats,
    /profile bounded capture).
"""

import json
import random

import numpy as np
import pytest

from test_kernel import GOD, random_schedule

from fluidframework_tpu.mergetree.host import GOD_CLIENT
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
from fluidframework_tpu.telemetry import counters, device_stats
from fluidframework_tpu.telemetry.compile_ledger import ledger


@pytest.fixture(autouse=True)
def _stats_on():
    """Every test here runs with the plane enabled and restores the
    process default after (other tests inherit the env default)."""
    prev = device_stats.enabled()
    device_stats.set_enabled(True)
    counters.reset()
    yield
    device_stats.set_enabled(prev)
    counters.reset()


class _Ctx:
    def checkpoint(self, *_):
        pass

    def error(self, err, restart=False):
        raise err


def _stream(builder, schedule):
    out = []
    for op in schedule:
        kind = op[0]
        if kind == "insert":
            _, pos, text, ref_seq, client, seq = op
            out.append(builder.insert_text(pos, text, ref_seq, client,
                                           seq))
        elif kind == "remove":
            _, start, end, ref_seq, client, seq = op
            out.append(builder.remove(start, end, ref_seq, client, seq))
        else:
            _, start, end, props, ref_seq, client, seq = op
            out.append(builder.annotate(start, end, props, ref_seq,
                                        client, seq))
    return out


# ---------------------------------------------------------------------------
# serving windows/bursts: bit-identity + exact reconciliation
# ---------------------------------------------------------------------------

def _storm_wave(wave: int, docs: int, ops_per_doc: int,
                storm_ops: int, ring_wave: int = -1):
    """Raw-wire wave: doc 0 is the storm doc (deep per-wave stream →
    multi-window flushes; at `ring_wave` it also annotates one span past
    the row's annotate ring → the rollback + host rescue path), the rest
    type keystrokes."""
    rng = random.Random(83 + wave)
    out = []
    for d in range(docs):
        doc = f"s{d}"
        n_ops = storm_ops if d == 0 else ops_per_doc
        base = wave * n_ops
        contents = []
        if wave == 0:
            contents.append(DocumentMessage(
                client_sequence_number=0, reference_sequence_number=-1,
                type=MessageType.CLIENT_JOIN,
                data=json.dumps({"clientId": f"c{d}", "detail": {}})))
        for i in range(n_ops):
            contents.append(DocumentMessage(
                client_sequence_number=base + i + 1,
                reference_sequence_number=base,
                type=MessageType.OPERATION,
                contents={"address": "s", "contents": {
                    "address": "t", "contents": {
                        "type": 0, "pos1": 0,
                        "seg": {"text": "z" * rng.randrange(1, 3)}}}}))
        if d == 0 and wave == ring_wave:
            for i in range(6):  # DEFAULT_ANNO_SLOTS=4
                contents.append(DocumentMessage(
                    client_sequence_number=base + n_ops + i + 1,
                    reference_sequence_number=base,
                    type=MessageType.OPERATION,
                    contents={"address": "s", "contents": {
                        "address": "t", "contents": {
                            "type": 2, "pos1": 0, "pos2": 2,
                            "props": {f"k{i}": i}}}}))
        out.append(QueuedMessage(
            topic="rawdeltas", partition=0, offset=wave * docs + d,
            key=doc,
            value=boxcar_to_wire(Boxcar(
                tenant_id="t", document_id=doc, client_id=f"c{d}",
                contents=contents))))
    return out


def _run_pipeline(waves, stats_on: bool):
    import jax

    counters.reset()
    device_stats.set_enabled(stats_on)
    emitted = []

    def on_window(window):
        for doc_id, msg in window.messages():
            emitted.append((doc_id, msg.sequence_number,
                            msg.minimum_sequence_number, msg.client_id,
                            msg.client_sequence_number))

    lam = TpuSequencerLambda(_Ctx(), emit=lambda *a: None,
                             nack=lambda *a: None, client_timeout_s=0.0)
    lam.emit_window = on_window
    lam.pipelined = True
    for wave in waves:
        for qm in wave:
            lam.handler(qm)
        lam.flush()
    lam.drain()
    import hashlib
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(lam.merge.pages.pool):
        h.update(np.asarray(leaf).tobytes())
    h.update(repr(sorted(lam.merge.pages.tables.items())).encode())
    for leaf in jax.tree_util.tree_leaves(lam.tstate):
        h.update(np.asarray(leaf).tobytes())
    snap = counters.snapshot()
    return emitted, h.hexdigest(), snap


class TestServingPlane:
    def test_bit_identity_and_exact_reconcile_contended(self):
        """Telemetry on vs off over a contended ragged fleet — a storm
        doc spanning several windows per flush, one of whose rings
        overflows an annotate ring (rollback + host rescue): the emit
        stream and the lane planes must be identical, the run must
        actually have exercised recovery, and every countable device
        slot must equal its host mirror exactly."""
        waves = [_storm_wave(w, docs=12, ops_per_doc=8, storm_ops=48,
                             ring_wave=2)
                 for w in range(4)]
        emits_off, digest_off, snap_off = _run_pipeline(waves, False)
        emits_on, digest_on, snap_on = _run_pipeline(waves, True)

        assert emits_off == emits_on
        assert digest_off == digest_on
        # The scenario is genuinely contended: overflow recovery ran.
        assert snap_on.get("serving.recovery_dispatches", 0) > 0
        # Exact device-vs-host reconciliation, with real activity.
        assert device_stats.reconcile() is None
        assert snap_on["device.serving.ticket_admitted"] > 0
        assert snap_on.get("device.serving.ops_insert", 0) \
            + snap_on.get("device.serving.ops_insert_run", 0) > 0
        for slot in device_stats.SERVE_SLOTS:
            dev = snap_on.get(f"device.serving.{slot}")
            host = snap_on.get(f"host.serving.{slot}")
            assert dev == host, (slot, dev, host)
        # The off run folded nothing.
        assert not any(k.startswith("device.serving.")
                       for k in snap_off)

    def test_stats_off_run_emits_no_device_counters(self):
        waves = [_storm_wave(0, docs=4, ops_per_doc=4, storm_ops=4)]
        _, _, snap = _run_pipeline(waves, False)
        assert not any(k.startswith(("device.", "host."))
                       for k in snap)


# ---------------------------------------------------------------------------
# paged apply plane
# ---------------------------------------------------------------------------

class TestPagedPlane:
    def test_paged_apply_reconciles_and_stays_bit_identical(self):
        rng = random.Random(5)
        schedules = {("doc", "s", "storm"): random_schedule(rng, 3, 90)}
        for i in range(6):
            schedules[("doc", "s", f"k{i}")] = random_schedule(rng, 2, 5)

        def run(stats_on):
            counters.reset()
            device_stats.set_enabled(stats_on)
            store = MergeLaneStore(page_rows=16)
            store.apply({k: _stream(store.builder, s)
                         for k, s in schedules.items()})
            texts = {k: store.text(k) for k in schedules}
            entries = {k: store.entries(k) for k in schedules}
            return texts, entries, counters.snapshot()

        t_off, e_off, snap_off = run(False)
        t_on, e_on, snap_on = run(True)
        assert t_off == t_on
        assert e_off == e_on
        assert not any(k.startswith("device.paged") for k in snap_off)
        # Exact per-kind reconciliation against the staged streams.
        total_dev = sum(snap_on.get(f"device.paged.{s}", 0)
                        for s in device_stats.PAGED_SLOTS[:6])
        assert total_dev > 0
        for slot in device_stats.PAGED_SLOTS[:7]:
            dev = snap_on.get(f"device.paged.{slot}", 0)
            host = snap_on.get(f"host.paged.{slot}", 0)
            assert dev == host, (slot, dev, host)
        assert snap_on.get("device.paged.reconcile_mismatch", 0) == 0


# ---------------------------------------------------------------------------
# extract plane: zamboni reclamation
# ---------------------------------------------------------------------------

class TestExtractPlane:
    def test_reclaimed_rows_reported_exactly(self):
        """Insert then remove with the collab window advanced past the
        removes: the fused zamboni+extract must report exactly the
        tombstoned rows as reclaimed (pre minus post counts from the
        device plane)."""
        store = MergeLaneStore()
        b = store.builder
        key = ("doc", "s", "gc")
        ops = [b.insert_text(0, "aaaa", 0, GOD_CLIENT, 1, msn=0),
               b.insert_text(4, "bbbb", 1, GOD_CLIENT, 2, msn=0),
               b.insert_text(8, "cccc", 2, GOD_CLIENT, 3, msn=0),
               # Remove the middle; msn advances past the remove seq so
               # the tombstone is zamboni-eligible at extract time.
               b.remove(4, 8, 3, GOD_CLIENT, 4, msn=4)]
        store.apply({key: ops})
        counters.reset()
        out = store.extract_all()
        assert store.text(key) == "aaaacccc"
        assert key in out
        snap = counters.snapshot()
        assert snap.get("device.extract.docs", 0) >= 1
        # Exactly one segment row (the removed middle) reclaimed.
        assert snap.get("device.extract.rows_reclaimed", 0) == 1
        # zamboni.rows_reclaimed belongs to the defrag tick ONLY —
        # disjoint from the extract counter, so the flush span can sum
        # the pair without double-counting.
        assert snap.get("zamboni.rows_reclaimed", 0) == 0

    def test_extract_plane_absent_when_disabled(self):
        device_stats.set_enabled(False)
        store = MergeLaneStore()
        b = store.builder
        store.apply({("d", "s", "t"): [
            b.insert_text(0, "hi", 0, GOD_CLIENT, 1)]})
        counters.reset()
        store.extract_all()
        assert not any(k.startswith("device.extract")
                       for k in counters.snapshot())


# ---------------------------------------------------------------------------
# compile ledger: warm/cold attribution
# ---------------------------------------------------------------------------

class TestCompileLedger:
    def test_warm_cold_attribution_pinned(self):
        import jax
        import jax.numpy as jnp

        from fluidframework_tpu.telemetry.counters import JitRetraceProbe

        name = "test.ledger_attr"
        probed = JitRetraceProbe(jax.jit(lambda x: x * 2 + 1), name=name)
        probed(jnp.ones((4,)))          # cold: first compile
        probed(jnp.ones((4,)))          # warm
        probed(jnp.ones((8,)))          # cold again: new shape = retrace
        probed(jnp.ones((8,)))          # warm
        sym = ledger.snapshot()["symbols"][name]
        assert sym["compiles"] == 2
        assert sym["retraces"] == 1
        assert sym["coldCalls"] == 2
        assert sym["warmCalls"] == 2
        assert sym["compileMs"] > 0.0
        assert sym["cacheSize"] == 2

    def test_track_context_attributes_first_compile(self):
        import jax
        import jax.numpy as jnp

        name = "test.ledger_track"
        fn = jax.jit(lambda x: x - 3)
        with ledger.track(name, fn):
            fn(jnp.ones((5,)))
        with ledger.track(name, fn):
            fn(jnp.ones((5,)))
        sym = ledger.snapshot()["symbols"][name]
        assert sym["compiles"] == 1
        assert sym["coldCalls"] == 1
        assert sym["warmCalls"] == 1
        assert sym["compileMs"] > 0.0

    def test_bench_stamp_shape(self):
        stamp = ledger.bench_stamp()
        assert {"total_compiles", "total_compile_ms", "retraces",
                "symbols"} <= set(stamp)


# ---------------------------------------------------------------------------
# /metrics.prom cardinality guard
# ---------------------------------------------------------------------------

class TestCardinalityGuard:
    def test_family_cap_with_overflow_bucket(self, monkeypatch):
        monkeypatch.setattr(counters, "FAMILY_CAP", 4)
        names = {counters.bounded("tenant.ops", f"t{i}")
                 for i in range(50)}
        # 4 distinct labels + the shared overflow bucket, never more.
        assert len(names) == 5
        assert "tenant.ops.__other__" in names
        assert counters.get("telemetry.metrics_dropped") == 46
        # A previously admitted label keeps its own name.
        assert counters.bounded("tenant.ops", "t0") == "tenant.ops.t0"

    def test_global_name_cap_collapses_new_names(self, monkeypatch):
        monkeypatch.setattr(counters, "MAX_COUNTER_NAMES", 8)
        for i in range(20):
            counters.increment(f"churn.docs.d{i}")
        snap = counters.snapshot()
        assert len(snap) <= 8 + 2  # cap + overflow bucket + drop counter
        assert snap["telemetry.metrics_dropped"] > 0
        assert "churn.docs.__other__" in snap
        # Existing names keep incrementing past the cap.
        before = counters.get("churn.docs.d0")
        counters.increment("churn.docs.d0")
        assert counters.get("churn.docs.d0") == before + 1

    def test_tenant_churn_soak_bounds_exposition(self, monkeypatch):
        from fluidframework_tpu.server.monitor import ServiceMonitor

        monkeypatch.setattr(counters, "FAMILY_CAP", 8)
        mon = ServiceMonitor().start()
        try:
            sizes = []
            for round_ in range(3):
                for i in range(200):
                    counters.increment(counters.bounded(
                        "soak.tenant", f"t{round_}_{i}"))
                sizes.append(len(mon.prometheus()))
            # The exposition stops growing once the family cap is hit.
            assert sizes[1] == sizes[2]
        finally:
            mon.stop()


# ---------------------------------------------------------------------------
# monitor surfaces
# ---------------------------------------------------------------------------

class TestMonitorSurfaces:
    def test_health_carries_ledger_and_device_stats(self):
        from fluidframework_tpu.server.monitor import ServiceMonitor

        counters.increment("device.serving.ops_insert", 3)
        counters.increment("host.serving.ops_insert", 3)
        mon = ServiceMonitor().start()
        try:
            health = mon.health()
            assert "compileLedger" in health
            assert {"symbols", "totals"} <= set(health["compileLedger"])
            assert health["deviceStats"][
                "device.serving.ops_insert"] == 3
            assert health["deviceReconcile"] is None
            counters.increment("device.serving.ops_insert", 2)
            health = mon.health()
            assert health["deviceReconcile"] == {
                "ops_insert": (5, 3)}
        finally:
            mon.stop()

    def test_prometheus_carries_compile_gauges(self):
        import jax
        import jax.numpy as jnp

        from fluidframework_tpu.server.monitor import ServiceMonitor
        from fluidframework_tpu.telemetry.counters import JitRetraceProbe

        probed = JitRetraceProbe(jax.jit(lambda x: x + 7),
                                 name="test.prom_sym")
        probed(jnp.ones((3,)))
        mon = ServiceMonitor().start()
        try:
            prom = mon.prometheus()
        finally:
            mon.stop()
        assert 'fluid_compile_compiles{symbol="test.prom_sym"}' in prom
        assert "fluid_compile_total_ms" in prom

    def test_profile_endpoint_captures_bounded_trace(self):
        import os
        import urllib.request

        from fluidframework_tpu.server.monitor import ServiceMonitor

        mon = ServiceMonitor().start()
        try:
            with urllib.request.urlopen(
                    mon.url + "/profile?ms=40") as resp:
                payload = json.loads(resp.read())
            assert payload["ok"] is True
            assert payload["durationMs"] == 40.0
            assert os.path.isdir(payload["dir"])
            assert payload["files"]
        finally:
            mon.stop()

    def test_profile_window_is_capped(self):
        from fluidframework_tpu.server.monitor import ServiceMonitor

        mon = ServiceMonitor().start()
        try:
            assert mon._PROFILE_MAX_MS <= 5000.0
        finally:
            mon.stop()


# ---------------------------------------------------------------------------
# span coverage catch-up (readpath / broadcaster / paged rescue)
# ---------------------------------------------------------------------------

class TestSpanCoverage:
    def test_catchup_publish_and_get_fill_histograms(self):
        from fluidframework_tpu.server.readpath import CatchupCache

        cache = CatchupCache()
        cache.publish("t", "d1", {"seq": 5, "channels": []})
        cache.get("t", "d1", head_seq=5)
        cache.get("t", "missing")
        hist = counters.latency_snapshot()
        assert hist["catchup.publish"]["count"] == 1
        assert hist["catchup.get"]["count"] == 2

    def test_broadcaster_shard_dwell_histogram_fills(self):
        from fluidframework_tpu.protocol.messages import (
            SequencedDocumentMessage)
        from fluidframework_tpu.server.lambdas.broadcaster import (
            BroadcasterLambda)
        from fluidframework_tpu.server.lambdas.base import LambdaContext

        class _BCtx(LambdaContext):
            def __init__(self):
                pass

            def checkpoint(self, *_):
                pass

        got = []
        lam = BroadcasterLambda(_BCtx(), shards=2)
        lam.join_room("doc", got.append)
        msg = SequencedDocumentMessage(
            client_id="c", sequence_number=1,
            minimum_sequence_number=0, client_sequence_number=1,
            reference_sequence_number=0,
            type=MessageType.OPERATION, contents=None)
        lam._route("doc", msg)
        assert lam.drain(timeout=5.0)
        assert got
        hist = counters.latency_snapshot()
        assert hist["broadcaster.shard_dwell"]["count"] == 1
        lam.close()

    def test_paged_rescue_fills_histogram(self):
        """Annotate-ring exhaustion takes the host rescue — the rescue
        must be visible as the serving.paged_rescue stage."""
        store = MergeLaneStore()
        b = store.builder
        key = ("doc", "s", "anno")
        ops = [b.insert_text(0, "abcdef", 0, GOD_CLIENT, 1)]
        for i in range(6):  # DEFAULT_ANNO_SLOTS=4 -> ring exhausts
            ops.append(b.annotate(0, 6, {f"k{i}": i}, 1, GOD_CLIENT,
                                  2 + i))
        store.apply({key: ops})
        assert store.paged_rescues >= 1
        hist = counters.latency_snapshot()
        assert hist["serving.paged_rescue"]["count"] >= 1
        assert counters.get("serving.paged_rescues") >= 1
