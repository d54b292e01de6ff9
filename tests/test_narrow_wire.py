"""Incremental narrow-wire summarization: fused zamboni+extract, the
int16 delta wire format, pow2-bucketed dirty gathers, and the summarize
blob cache (dirty-epoch extraction).

Locks the PR's acceptance properties:
- compact->extract is bit-identical to extract on uncompacted state
  (oracle-locked via the randomized kernel traces);
- the narrow (int16 delta) fetch decodes to the EXACT int32 arrays the
  wide fetch returns, including the per-doc overflow refetch path;
- extraction D2H bytes drop >= 40% vs the int32 format (byte-counting);
- the dirty-lane gather does not recompile per distinct dirty count
  (JitRetraceProbe regression);
- fold/rescue paths advance the change generation, so dirty-epoch
  extraction never serves a stale cached blob for a touched lane.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from fluidframework_tpu.mergetree import kernel
from fluidframework_tpu.mergetree.constants import (
    DEV_NO_REMOVE,
    DEV_UNASSIGNED,
)
from fluidframework_tpu.mergetree.oppack import PackedOps
from fluidframework_tpu.mergetree.state import make_state, state_from_numpy
from fluidframework_tpu.telemetry import counters


_STATE_CACHE = {}


def _traced_state(docs=32, n_ops=16, capacity=64, seed=11, anno_slots=2):
    """A batch of states driven by the synthetic bench traces (insert/
    remove mix) — the same op shapes the oracle conformance suite
    replays. Cached per arg tuple: every distinct shape costs a scan-
    kernel compile, which dominates this file's runtime on CPU."""
    key = (docs, n_ops, capacity, seed, anno_slots)
    if key not in _STATE_CACHE:
        from fluidframework_tpu.testing.traces import gen_traces

        cols = gen_traces(docs, n_ops, seed=seed)
        ops = PackedOps(
            **{f: jnp.asarray(cols[f]) for f in PackedOps._fields})
        _STATE_CACHE[key] = kernel.apply_ops_batched_keep(
            make_state(capacity, anno_slots, batch=docs), ops)
    return _STATE_CACHE[key]


def _rows_equal(a_packed, b_packed):
    """Per-doc live-row equality of two fetched extraction tuples."""
    counts = np.asarray(a_packed[-1])
    assert np.array_equal(counts, np.asarray(b_packed[-1]))
    for i, (a, b) in enumerate(zip(a_packed[:-1], b_packed[:-1])):
        for d in range(len(counts)):
            n = counts[d]
            assert np.array_equal(a[d, :n], b[d, :n]), (i, d)


class TestFusedCompactExtract:
    def test_compact_state_bit_identical(self):
        state = _traced_state()
        fused_state, _ = kernel.compact_extract_batched(state)
        plain = kernel.compact_batched(state)
        for name, a, b in zip(state._fields, plain, fused_state):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name

    def test_extract_equals_uncompacted_extract(self):
        """The oracle-locked equivalence: extracting AFTER zamboni
        returns the same live rows as extracting the uncompacted state
        (extraction's keep-mask IS compaction's keep-mask)."""
        state = _traced_state(seed=11)
        _, fused_packed = kernel.compact_extract_batched(state)
        plain_packed = kernel.extract_visible_batched(state)
        _rows_equal(kernel.fetch_extracted(plain_packed, narrow=False),
                    kernel.fetch_extracted(fused_packed, narrow=False))

    def test_extract_after_explicit_compact_matches(self):
        state = _traced_state(seed=11)
        compacted = kernel.compact_batched(state)
        _rows_equal(
            kernel.fetch_extracted(
                kernel.extract_visible_batched(state), narrow=False),
            kernel.fetch_extracted(
                kernel.extract_visible_batched(compacted), narrow=False))


class TestNarrowWire:
    def test_narrow_decode_bit_identical(self):
        state = _traced_state(seed=11)
        _, packed = kernel.compact_extract_batched(state)
        _rows_equal(kernel.fetch_extracted(packed, narrow=True),
                    kernel.fetch_extracted(packed, narrow=False))

    def test_byte_drop_at_least_40pct(self):
        state = _traced_state(seed=11)
        _, packed = kernel.compact_extract_batched(state)
        b0 = counters.get("summarize.bytes_d2h")
        kernel.fetch_extracted(packed, narrow=True)
        narrow_bytes = counters.get("summarize.bytes_d2h") - b0
        b0 = counters.get("summarize.bytes_d2h")
        kernel.fetch_extracted(packed, narrow=False)
        wide_bytes = counters.get("summarize.bytes_d2h") - b0
        assert narrow_bytes > 0 and wide_bytes > 0
        assert narrow_bytes <= 0.6 * wide_bytes, (narrow_bytes, wide_bytes)

    def _wide_span_batch(self):
        """Doc 0's seq span exceeds int16 (forces the exact-plane
        refetch); doc 1 stays narrow."""
        cols = {
            "length": np.array([3, 4, 5, 2], np.int32),
            "ins_seq": np.array([1, 100000, 5, DEV_UNASSIGNED], np.int32),
            "ins_client": np.array([0, 1, 2, 3], np.int32),
            "rem_seq": np.array(
                [DEV_NO_REMOVE, 99999, DEV_UNASSIGNED, DEV_NO_REMOVE],
                np.int32),
            "origin_op": np.array([7, 8, 9, 10], np.int32),
            "origin_off": np.array([0, 1, 2, 3], np.int32),
            "rem_client": np.array([-1, 4, 5, -1], np.int32),
        }
        row = state_from_numpy(cols, 16, anno_slots=2)._replace(
            min_seq=jnp.asarray(0, jnp.int32),
            seq=jnp.asarray(100000, jnp.int32))
        row2 = state_from_numpy(
            {"length": np.array([2], np.int32),
             "ins_seq": np.array([3], np.int32),
             "ins_client": np.array([0], np.int32),
             "origin_op": np.array([1], np.int32)}, 16, anno_slots=2)
        tm = jax.tree_util.tree_map
        return tm(lambda a, b: jnp.stack([a, b]) if a.ndim else
                  jnp.stack([a, b]), row, row2)

    def test_overflow_doc_refetches_exact_planes(self):
        batch = self._wide_span_batch()
        packed = kernel.extract_visible_batched(batch)
        r0 = counters.get("summarize.wire_refetch")
        narrow = kernel.fetch_extracted(packed, narrow=True)
        assert counters.get("summarize.wire_refetch") - r0 == 1
        _rows_equal(narrow, kernel.fetch_extracted(packed, narrow=False))

    def test_pending_and_sentinel_rows_round_trip(self):
        """DEV_UNASSIGNED / DEV_NO_REMOVE sentinels survive the narrow
        encode exactly (they are codes, not deltas)."""
        batch = self._wide_span_batch()
        packed = kernel.extract_visible_batched(batch)
        narrow = kernel.fetch_extracted(packed, narrow=True)
        (op32, off, length, anno, ins_seq, ins_client, rem_seq,
         rem_client, counts) = narrow
        assert counts[0] == 4
        assert ins_seq[0, 3] == DEV_UNASSIGNED
        assert rem_seq[0, 0] == DEV_NO_REMOVE
        assert rem_seq[0, 2] == DEV_UNASSIGNED


class TestGatherRowsPow2:
    def test_padding_and_rows(self):
        state = _traced_state(seed=11)
        sub, n = kernel.gather_rows_pow2(state, [1, 4, 7])
        assert n == 3
        assert sub.length.shape[0] == 4
        tm = jax.tree_util.tree_map
        for j, row in enumerate((1, 4, 7)):
            want = tm(lambda x: x[row], state)
            got = tm(lambda x: x[j], sub)
            for name, a, b in zip(state._fields, want, got):
                assert np.array_equal(np.asarray(a), np.asarray(b)), name

    def test_no_retrace_across_dirty_counts(self):
        """Distinct dirty counts under one pow2 bucket share a compiled
        program; crossing buckets compiles once per bucket — never a
        retrace per count (the hazard bench.py's extract_dirty carried
        before pow2 padding)."""
        state = _traced_state(seed=11)
        # Warm every pow2 bucket this test will touch.
        for n in (1, 2, 4, 8):
            kernel.gather_rows_pow2(state, list(range(n)))
        before = counters.get("kernel.extract_gather.retraces")
        for n in (3, 5, 6, 7, 2, 1, 4, 8, 5, 3):
            sub, got_n = kernel.gather_rows_pow2(state, list(range(n)))
            assert got_n == n
        assert counters.get("kernel.extract_gather.retraces") == before


class TestDirtyEpochNeverStale:
    def _store(self, page_rows=None):
        from fluidframework_tpu.server.tpu_sequencer import MergeLaneStore
        return MergeLaneStore(page_rows=page_rows)

    def _text_of(self, snap):
        return "".join(e.get("text") or "" for c in snap["chunks"]
                       for e in c if e.get("removedSeq") is None)

    def test_clean_lane_rides_cache_dirty_lane_reassembles(self):
        store = self._store()
        a, b = ("d", "s", "a"), ("d", "s", "b")
        store.apply({a: [store.builder.insert_text(0, "alpha ", 0, 0, 1)],
                     b: [store.builder.insert_text(0, "beta ", 0, 0, 1)]})
        first = store.extract_all()
        assert store.dirty_keys() == set()
        h0 = counters.get("summarize.blob_cache.hits")
        second = store.extract_all()
        assert second == first
        assert counters.get("summarize.blob_cache.hits") - h0 == 2
        store.apply({a: [store.builder.insert_text(0, "X", 1, 0, 2)]})
        assert store.dirty_keys() == {a}
        third = store.extract_all()
        assert self._text_of(third[a]) == "Xalpha "
        assert third[b] == first[b]

    def test_fold_crowded_marks_dirty(self):
        """The page compaction tick left-packs a lane's rows and releases
        emptied pages without any new op: whatever a summarize serves
        afterwards — the cached blob or a fresh extraction — must equal a
        forced fresh extraction of the compacted pages."""
        store = self._store(page_rows=16)
        key = ("d", "s", "t")
        seq = 0
        for i in range(120):
            seq += 1
            store.apply({key: [store.builder.insert_text(
                0, "x", seq - 1, 0, seq)]})
        # Remove most of it, then advance min_seq past the remove so the
        # tombstones are zamboni-eligible.
        seq += 1
        store.apply({key: [store.builder.remove(0, 110, seq - 1, 0, seq)]})
        seq += 1
        store.apply({key: [store.builder.insert_text(
            0, "!", seq - 1, 0, seq, msn=seq - 1)]})
        expect = store.text(key)
        first = store.extract_all()
        assert self._text_of(first[key]) == expect
        seq += 1
        store.apply({key: [store.builder.insert_text(
            len(expect), "?", seq - 1, 0, seq, msn=seq - 1)]})
        expect = store.text(key)
        store.compact_all()
        assert store.page_compactions >= 1
        after = store.extract_all()
        assert self._text_of(after[key]) == expect
        with store._guard_lock:
            store._snap_cache.clear()
        assert store.extract_all()[key] == after[key]

    def test_rescue_lane_marks_dirty(self, monkeypatch):
        """_rescue_paged reseeds a lane wholesale; a summarize
        immediately after must re-extract, not serve the pre-rescue
        blob."""
        store = self._store(page_rows=16)
        key = ("d", "s", "t")
        seq = 0
        for i in range(4):
            seq += 1
            store.apply({key: [store.builder.insert_text(
                0, "ab", seq - 1, 0, seq)]})
        store.extract_all()  # populate the cache
        gen_before = store.change_gen.get(key, 0)
        row = store.pages.row(key)
        seq += 1
        ops = [store.builder.insert_text(0, "Z", seq - 1, 0, seq)]
        assert store._rescue_paged(key, row, ops)
        assert store.change_gen.get(key, 0) > gen_before
        snap = store.extract_all()[key]
        assert self._text_of(snap).startswith("Z")

    def test_dropped_lane_evicts_cache(self):
        store = self._store()
        key = ("d", "s", "t")
        store.apply({key: [store.builder.insert_text(0, "gone", 0, 0, 1)]})
        store.extract_all()
        assert key in store._snap_cache
        store.drop(key)
        assert key not in store._snap_cache
        assert key not in store.last_summarized_gen
        assert store.extract_all() == {}


class TestMonitorSummaryProbe:
    def test_watch_summaries_reports(self):
        from fluidframework_tpu.server.monitor import ServiceMonitor
        store = TestDirtyEpochNeverStale()._store()
        key = ("d", "s", "t")
        store.apply({key: [store.builder.insert_text(0, "hi", 0, 0, 1)]})
        mon = ServiceMonitor(port=0).start()
        try:
            mon.watch_summaries("summaries", store)
            report = mon.report()["probes"]["summaries"]
            assert report["dirtyLanes"] == 1  # never summarized yet
            store.extract_all()
            report = mon.report()["probes"]["summaries"]
            assert report["dirtyLanes"] == 0
            assert report["cachedBlobs"] == 1
            assert 0.0 <= report["blobCacheHitRate"] <= 1.0
            health = mon.health()
            assert "summarize.bytes_d2h" in health["counters"]
        finally:
            mon.stop()
