"""The read tier's narrow artifact built straight from the extraction's
columns (mergetree.catchup.pack_extracted_narrow) is byte for byte the
blob the dict path builds from host.assemble_snapshot's entries
(translate_entry_clients, then pack_entries_narrow), on every shape a
merge lane holds; late clients adopt it; and a refresh neither reads
nor writes the summarize blob cache, nor moves its epoch."""

import random

import numpy as np
import pytest

from fluidframework_tpu.dds.matrix import SharedMatrix
from fluidframework_tpu.dds.sequence import SharedNumberSequence, SharedString
from fluidframework_tpu.loader.container import Loader
from fluidframework_tpu.loader.drivers.local import (
    LocalDocumentServiceFactory,
)
from fluidframework_tpu.mergetree import kernel
from fluidframework_tpu.mergetree.catchup import (
    pack_entries_narrow,
    pack_extracted_narrow,
    translate_entry_clients,
    unpack_entries_narrow,
)
from fluidframework_tpu.mergetree.host import (
    PayloadTable,
    assemble_snapshot,
    chunk_ends,
    chunk_entries,
    flatten_snapshot_content,
)
from fluidframework_tpu.mergetree.runs import Run
from fluidframework_tpu.server.local_server import TpuLocalServer
from fluidframework_tpu.server.tpu_sequencer import (
    MATRIX_COLS_SUFFIX,
    MATRIX_ROWS_SUFFIX,
)
from fluidframework_tpu.telemetry import counters

DOC, STORE, CHAN = "doc", "default", "x"


class Session:
    """One document with one channel of `dds` on a paged TpuLocalServer,
    written by `writers` connected clients."""

    def __init__(self, dds, writers=1, seed=3):
        self.server = TpuLocalServer(paged_lanes=True)
        self.loader = Loader(LocalDocumentServiceFactory(self.server))
        creator = self.loader.create_detached(DOC)
        creator.runtime.create_datastore(STORE).create_channel(CHAN,
                                                               dds.TYPE)
        creator.attach()
        self.creator = creator
        self.chans = [self._channel(creator)]
        for _ in range(writers - 1):
            self.chans.append(self._channel(self.loader.resolve(DOC)))
        self.rng = random.Random(seed)
        self.wire = {}  # _both_ways arguments of the case's own check

    @staticmethod
    def _channel(container):
        return container.runtime.get_datastore(STORE).get_channel(CHAN)

    def writer(self):
        return self.rng.choice(self.chans)

    def late(self, replay=False):
        """A late client's channel; `replay` takes the read tier away."""
        saved = self.server.catchup
        if replay:
            self.server.catchup = None
        try:
            return self._channel(self.loader.resolve(DOC))
        finally:
            self.server.catchup = saved


def _typed(s, n, props=None):
    for i in range(n):
        t = s.writer()
        t.insert_text(s.rng.randrange(t.get_length() + 1), f"<{i}>",
                      props(i) if props else None)


def _text(s):
    _typed(s, 60)


def _markers(s):
    _typed(s, 20)
    for i in range(15):
        t = s.writer()
        t.insert_marker(s.rng.randrange(t.get_length() + 1),
                        {"m": i} if i % 2 else None)


def _props(s):
    _typed(s, 30, props=lambda i: {"font": i} if i % 3 == 0 else None)
    for i in range(25):
        t = s.writer()
        a = s.rng.randrange(t.get_length() - 4)
        t.annotate_range(a, a + 4, {"b": i, "c": -i})
        if i % 4 == 0:  # a key cleared to None inside the chain
            t.annotate_range(a + 1, a + 3, {"b": None})


def _removed(s):
    _typed(s, 50)
    for _ in range(20):
        t = s.writer()
        a = s.rng.randrange(t.get_length() - 3)
        t.remove_text(a, a + s.rng.randrange(1, 4))


def _min_seq(s):
    _typed(s, 40)
    s.server.pump()
    _typed(s, 15)  # rows at min_seq and below beside rows above it


def _escapes(s):
    # Held back from the sequencer, both writers' edits sequence above
    # min_seq, so every seq and client field is present somewhere.
    s.server.auto_pump = False
    _removed(s)
    _typed(s, 10)
    s.server.auto_pump = True
    # Values past the int16 window: a base seq 40,000 past the head, and
    # 40,000 table entries before the document's own clients.
    s.wire = {"shift": 40000, "pad": 40000}


def _matrix(s):
    for step in range(60):
        m = s.writer()
        r, c = m.row_count, m.col_count
        act = s.rng.random()
        if act < 0.25 or r == 0:
            m.insert_rows(s.rng.randint(0, r), s.rng.randint(1, 3))
        elif act < 0.5 or c == 0:
            m.insert_cols(s.rng.randint(0, c), s.rng.randint(1, 2))
        elif act < 0.6 and r > 1:
            m.remove_rows(s.rng.randrange(r - 1), 1)
        elif act < 0.7 and c > 1:
            m.remove_cols(s.rng.randrange(c - 1), 1)
        else:
            m.set_cell(s.rng.randrange(r), s.rng.randrange(c), step)


def _items(s):
    for i in range(40):
        q = s.writer()
        q.insert_range(s.rng.randrange(q.get_length() + 1), [i, i * 10])
    for _ in range(8):
        q = s.writer()
        a = s.rng.randrange(q.get_length() - 2)
        q.remove_range(a, a + 2)


def _empty(s):
    _typed(s, 5)
    s.wire = {"empty": True}  # the extraction read as holding no rows


CASES = {
    "text": (SharedString, 1, _text),
    "markers": (SharedString, 2, _markers),
    "props": (SharedString, 2, _props),
    "removed": (SharedString, 2, _removed),
    "min_seq": (SharedString, 2, _min_seq),
    "escapes": (SharedString, 2, _escapes),
    "matrix": (SharedMatrix, 2, _matrix),
    "items": (SharedNumberSequence, 2, _items),
    "empty": (SharedString, 1, _empty),
    "unmapped": (SharedString, 2, _removed),
}


def _both_ways(lam, shift=0, pad=0, drop=(), empty=False,
               chunk_chars=10000):
    """Each merge lane of DOC at one extraction: (columnar, dict path),
    each a thunk giving the lane's (header, narrow blob). `shift` moves
    the base seq past the head, `pad` puts table entries before the
    document's clients, `drop` leaves ordinals out of the table, and
    `empty` zeroes the extraction's row counts."""
    keys = {k for k in lam.merge.where if k[0] == DOC}
    jobs, cached = lam.merge.extract_dispatch(only=keys, blob_cache=False)
    assert not cached
    base = lam.document_seq(DOC) + shift
    ordinals = np.array([*range(-pad, 0),
                         *(o for o in sorted(lam.docs[DOC].ordinals)
                           if o not in drop)], np.int64)
    mapping = {o: i for i, o in enumerate(ordinals.tolist())}
    table = lam.merge.payloads
    out = {}
    for packed, lanes, seqs, mins, _gens in jobs:
        packed = kernel.fetch_extracted(packed)
        if empty:
            packed = (*packed[:-1], np.zeros_like(packed[-1]))
        for lane, key in lanes:
            kw = dict(min_seq=int(np.asarray(mins)[lane]),
                      seq=int(np.asarray(seqs)[lane]))
            snap = assemble_snapshot(packed, table, lane,
                                     chunk_chars=chunk_chars, **kw)
            entries = [e for chunk in snap["chunks"] for e in chunk]
            out[key] = (
                lambda p=packed, l=lane, kw=kw: pack_extracted_narrow(
                    p, table, l, base_seq=base, ordinals=ordinals,
                    chunk_chars=chunk_chars, **kw),
                lambda h=snap["header"], e=entries: (
                    h, pack_entries_narrow(translate_entry_clients(
                        e, mapping), base)))
    assert set(out) == keys
    return out


def _served_lanes(body) -> dict:
    """A document's artifact body -> {merge lane key: (header, blob)}."""
    out = {}
    for store, chan, header, blob in body["channels"]:
        if header.get("kind") == "matrix":
            for suffix, axis in ((MATRIX_ROWS_SUFFIX, "rows"),
                                 (MATRIX_COLS_SUFFIX, "cols")):
                out[(DOC, store, chan + suffix)] = (header[axis],
                                                    blob[axis])
        else:
            out[(DOC, store, chan)] = (header, blob)
    return out


def _content(chan):
    """What a client's channel shows: the grid, the items, or the
    text's (char, props) stream."""
    if isinstance(chan, SharedMatrix):
        return chan.extract()
    if isinstance(chan, SharedNumberSequence):
        return chan.get_items()
    return [(ch, sorted((e.get("props") or {}).items()))
            for e in chan.client.tree.snapshot_segments()
            if e.get("removedSeq") is None
            for ch in (e.get("text") if e.get("kind", 0) == 0 else "\n")]


@pytest.mark.parametrize("case", sorted(CASES))
def test_columnar_blob_equals_dict_path(case):
    dds, writers, build = CASES[case]
    s = Session(dds, writers)
    build(s)
    s.server.pump()
    lam = s.server.sequencer()
    lam.drain()
    if case == "unmapped":
        # An ordinal the lanes hold but the document's table lacks: both
        # paths raise, and the refresh skips the document.
        ordinal = max(lam.docs[DOC].ordinals)
        for columnar, dict_path in _both_ways(lam, drop={ordinal}).values():
            with pytest.raises(KeyError):
                dict_path()
            with pytest.raises(KeyError):
                columnar()
        del lam.docs[DOC].ordinals[ordinal]
        skipped = counters.get("catchup.refresh_unsupported")
        assert DOC not in lam.catchup_snapshot()
        assert counters.get("catchup.refresh_unsupported") - skipped == 1
        return

    columnar0 = counters.get("catchup.columnar_lanes")
    served = _served_lanes(lam.catchup_snapshot()[DOC])
    assert counters.get("catchup.columnar_lanes") - columnar0 == len(served)
    ways = _both_ways(lam)
    assert set(served) == set(ways)
    for key, (columnar, dict_path) in ways.items():
        assert served[key] == columnar() == dict_path()
    # Small chunks, so the header's chunk count is tested too.
    for columnar, dict_path in _both_ways(lam, chunk_chars=7,
                                          **s.wire).values():
        header, blob = columnar()
        assert (header, blob) == dict_path()
        if case == "text":
            assert header["chunkCount"] > 2
        if s.wire.get("empty"):
            assert blob["n"] == 0 and header["chunkCount"] == 1
        elif s.wire:
            assert all(blob[x] for x in ("seqX", "cliX", "remX", "rclX"))

    entries = [e for _, blob in served.values()
               for e in unpack_entries_narrow(blob)]
    shape = {
        "markers": lambda: any(e["kind"] == 1 for e in entries),
        "props": lambda: any(e.get("props") for e in entries),
        "removed": lambda: any("removedSeq" in e for e in entries),
        "min_seq": lambda: any("seq" in e for e in entries)
        and any("seq" not in e for e in entries),
        "matrix": lambda: any(isinstance(e["text"], dict)
                              and "run" in e["text"] for e in entries),
        "items": lambda: any(isinstance(e["text"], dict)
                             and "items" in e["text"] for e in entries),
    }
    assert shape.get(case, lambda: bool(entries))()

    # Late clients adopt the artifact and read what the writers and a
    # full replay read.
    assert s.server.refresh_catchup()["published"] == 1
    adopted = counters.get("catchup.client.adopted")
    late = s.late()
    assert counters.get("catchup.client.adopted") - adopted == 1
    assert _content(late) == _content(s.late(replay=True)) \
        == _content(s.chans[0])


def _typed_session():
    s = Session(SharedString, 2, seed=8)
    _props(s)
    _removed(s)
    s.server.pump()
    return s


def test_refresh_leaves_the_summarize_blob_cache_alone():
    """A refresh through the columnar path builds no snapshot dict, so it
    neither caches one nor advances the summarize epoch; a later server
    summarize extracts for itself and returns what a server that never
    refreshed returns."""
    s, twin = _typed_session(), _typed_session()
    key = (DOC, STORE, CHAN)
    lam = s.server.sequencer()
    lam.summarize_documents()
    gen0 = lam.merge.last_summarized_gen[key]
    for session in (s, twin):
        session.chans[0].insert_text(0, "later")
        session.server.pump()
    gen1 = lam.merge.change_gen[key]
    assert gen1 > gen0
    assert s.server.refresh_catchup()["published"] == 1
    assert lam.merge.last_summarized_gen[key] == gen0
    assert lam.merge._snap_cache[key][0] == gen0
    assert key in lam.merge.dirty_keys()

    hits = counters.get("summarize.blob_cache.hits")
    misses = counters.get("summarize.blob_cache.misses")
    snap = lam.summarize_documents()[key]
    assert counters.get("summarize.blob_cache.hits") == hits
    assert counters.get("summarize.blob_cache.misses") - misses == 1
    assert lam.merge.last_summarized_gen[key] == gen1
    assert snap == twin.server.sequencer().summarize_documents()[key]
    assert snap["header"]["sequenceNumber"] == s.server.sequence_number(DOC)
    assert "".join(ch for ch, _ in flatten_snapshot_content(snap)) \
        == s.chans[0].get_text()
    assert s.server.write_materialized_snapshots()[DOC]


def test_refresh_after_a_summarize_extracts_every_lane():
    """A refresh takes no blob-cache hits: after a summarize cached both
    channels and one of them was edited, the refresh extracts both and
    packs both from the columns, into the blobs the dict path gives."""
    s = Session(SharedString, 2, seed=5)
    _props(s)
    other = s.creator.runtime.get_datastore(STORE).create_channel(
        "other", SharedString.TYPE)
    other.insert_text(0, "other")
    s.server.pump()
    lam = s.server.sequencer()
    lam.summarize_documents()
    assert lam.merge.cached_blob_count() == 2
    other.insert_text(0, "edited ")
    s.server.pump()
    columnar0 = counters.get("catchup.columnar_lanes")
    hits = counters.get("summarize.blob_cache.hits")
    served = _served_lanes(lam.catchup_snapshot()[DOC])
    assert counters.get("catchup.columnar_lanes") - columnar0 == 2
    assert counters.get("summarize.blob_cache.hits") == hits
    assert len(served) == 2
    for key, (columnar, dict_path) in _both_ways(lam).items():
        assert served[key] == columnar() == dict_path()


def test_chunk_ends_cut_where_the_chunk_rule_says():
    """chunk_ends (the header's chunk count and chunk_entries' cuts)
    agree with the rule written out: each entry weighs max(1, its text
    size) and a chunk closes once its weight reaches chunk_chars, at and
    around every chunk boundary."""
    rng = random.Random(2)
    for _ in range(300):
        sizes = [rng.choice((0, 1, 2, 3, 5))
                 for _ in range(rng.randrange(30))]
        entries = [{"text": "a" * k} for k in sizes]
        for chunk_chars in (0, 1, 4, 6, 10):
            ends, weight = [], 0
            for i, k in enumerate(sizes):
                weight += max(1, k)
                if weight >= chunk_chars:
                    ends.append(i + 1)
                    weight = 0
            if not ends or ends[-1] < len(sizes):
                ends.append(len(sizes))
            assert chunk_ends(sizes, chunk_chars) == ends
            cut = chunk_entries(entries, chunk_chars)
            assert [len(c) for c in cut] == \
                [b - a for a, b in zip([0] + ends, ends)]
            assert [e for c in cut for e in c] == entries


def test_run_rows_past_their_payload_clamp_as_run_slicing_does():
    """A row reaching past its Run payload's end gets the width Run's own
    slice gives it, on both paths."""
    table = PayloadTable()
    op = table.add_insert(0, Run((7, 1), 10, 3))
    rows = [(0, 2), (2, 5), (4, 1)]  # (offset, length); run length 3
    n = len(rows)

    def col(values):
        return np.array([values], np.int32)

    packed = (col([op] * n), col([o for o, _ in rows]),
              col([ln for _, ln in rows]), np.full((1, n, 4), -1, np.int32),
              col([5] * n), col([0] * n), col([2**31 - 2] * n),
              col([-1] * n), np.array([n], np.int32))
    snap = assemble_snapshot(packed, table, 0, min_seq=1, seq=9)
    entries = [e for chunk in snap["chunks"] for e in chunk]
    header, blob = pack_extracted_narrow(
        packed, table, 0, min_seq=1, seq=9, base_seq=9,
        ordinals=np.array([0], np.int64))
    assert (header, blob) == (snap["header"], pack_entries_narrow(
        translate_entry_clients(entries, {0: 0}), 9))
    assert [p["run"] for _, p in blob["payloads"]] == \
        [[7, 1, 10, 2], [7, 1, 12, 1], [7, 1, 13, 0]]
