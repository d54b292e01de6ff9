"""The delta store's range reads: `find_range` (and `query_deltas` over it)
on the in-memory Collection and on SqliteCollection must return exactly
what the predicate scan returned before the index — rows with
from_seq < seq <= to_seq of one document, ordered by sequence number,
as fresh dicts — whatever order the rows arrived in."""

import random

import pytest

from fluidframework_tpu.server.database import Collection, DatabaseManager
from fluidframework_tpu.server.durable import SqliteDatabaseManager
from fluidframework_tpu.server.lambdas.scriptorium import (
    delta_key,
    query_deltas,
)
from fluidframework_tpu.telemetry import counters

BACKENDS = ["memory", "sqlite"]


@pytest.fixture
def make_deltas(tmp_path):
    def make(backend):
        if backend == "memory":
            db = DatabaseManager()
        else:
            db = SqliteDatabaseManager(str(tmp_path / "deltas.sqlite"))
        return db.collection("deltas", unique_key=delta_key,
                             range_key=delta_key)
    return make


def _row(doc, seq, tag=""):
    return {"documentId": doc, "sequence_number": seq,
            "contents": f"{doc}:{seq}{tag}"}


def _scan(deltas, doc, from_seq=0, to_seq=None):
    """The delta store's read before the index: a predicate over every
    row, then a sort."""
    hi = to_seq if to_seq is not None else 2 ** 62
    out = deltas.find(lambda d: d["documentId"] == doc
                      and from_seq < d["sequence_number"] <= hi)
    out.sort(key=lambda d: d["sequence_number"])
    return out


def _ranges(head):
    """Every bound shape worth a read: empty, open-ended, at and past the
    head, inverted, and a sweep of windows."""
    out = [(0, None), (0, 0), (head, None), (head + 5, None),
           (head - 1, None), (head, head), (7, 3), (-1, None), (0, head)]
    out += [(lo, lo + w) for lo in range(0, head + 2, 3) for w in (0, 1, 4)]
    return out


def _in_order(deltas):
    for seq in range(1, 41):
        assert deltas.insert_one(_row("a", seq))
    return {"a": 40}


def _out_of_order(deltas):
    # An at-least-once replay lands rows below the document's max.
    for seq in list(range(1, 21)) + [35, 36, 37]:
        assert deltas.insert_one(_row("a", seq))
    for seq in [30, 25, 21, 34, 22, 28, 23, 24, 26, 27, 29, 31, 32, 33]:
        assert deltas.insert_one(_row("a", seq))
    return {"a": 37}


def _duplicates(deltas):
    for seq in range(1, 21):
        assert deltas.insert_one(_row("a", seq))
    for seq in (1, 7, 20, 13):
        assert not deltas.insert_one(_row("a", seq, "-dup"))
    return {"a": 20}


def _interleaved(deltas):
    rng = random.Random(3)
    heads = {"a": 0, "b": 0, "c": 0}
    for _ in range(90):
        doc = rng.choice("abc")
        heads[doc] += 1
        assert deltas.insert_one(_row(doc, heads[doc]))
    return heads


SCENARIOS = {"in_order": _in_order, "out_of_order": _out_of_order,
             "duplicates": _duplicates, "interleaved": _interleaved}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("backend", BACKENDS)
def test_range_read_equals_scan(make_deltas, backend, scenario):
    deltas = make_deltas(backend)
    heads = SCENARIOS[scenario](deltas)
    for doc in list(heads) + ["absent"]:
        for lo, hi in _ranges(heads.get(doc, 0)):
            want = _scan(deltas, doc, lo, hi)
            assert deltas.find_range(doc, lo, hi) == want, (doc, lo, hi)
            assert query_deltas(deltas, doc, lo, hi) == want, (doc, lo, hi)
    if scenario == "duplicates":
        # The rejected replays are absent: one row per seq, the first.
        rows = query_deltas(deltas, "a")
        assert [r["sequence_number"] for r in rows] == list(range(1, 21))
        assert not any(r["contents"].endswith("-dup") for r in rows)


@pytest.mark.parametrize("backend", BACKENDS)
def test_range_read_returns_copies(make_deltas, backend):
    deltas = make_deltas(backend)
    for seq in range(1, 6):
        deltas.insert_one(_row("a", seq))
    rows = query_deltas(deltas, "a", 1, 3)
    rows[0]["contents"] = "mutated"
    rows.clear()
    again = query_deltas(deltas, "a", 1, 3)
    assert [r["contents"] for r in again] == ["a:2", "a:3"]


@pytest.mark.parametrize("seed", [11, 12])
@pytest.mark.parametrize("backend", BACKENDS)
def test_random_inserts_match_brute_force(make_deltas, backend, seed):
    """500 seeded inserts over 3 documents: at the head, past a gap,
    below the max, and replays of stored rows, against a brute-force
    filter of what was accepted."""
    rng = random.Random(seed)
    deltas = make_deltas(backend)
    accepted = {}
    nxt = {"a": 1, "b": 1, "c": 1}
    for _ in range(500):
        doc = rng.choice("abc")
        stored = [s for d, s in accepted if d == doc]
        missing = [s for s in range(1, nxt[doc])
                   if (doc, s) not in accepted]
        r = rng.random()
        if stored and r < 0.15:
            seq = rng.choice(stored)  # a replay of a stored row
        elif missing and r < 0.4:
            seq = rng.choice(missing)  # below the max: out of order
        else:
            seq = nxt[doc] + rng.randrange(0, 3)  # the head, or past a gap
        row = _row(doc, seq, f"#{rng.randrange(1000)}")
        assert deltas.insert_one(row) == ((doc, seq) not in accepted)
        accepted.setdefault((doc, seq), row)
        nxt[doc] = max(nxt[doc], seq + 1)
    for _ in range(200):
        doc = rng.choice("abcx")
        lo = rng.randrange(-2, nxt.get(doc, 3) + 2)
        hi = None if rng.random() < 0.2 else lo + rng.randrange(-2, 40)
        want = sorted((r for (d, s), r in accepted.items()
                       if d == doc and lo < s
                       and (hi is None or s <= hi)),
                      key=lambda r: r["sequence_number"])
        assert query_deltas(deltas, doc, lo, hi) == want, (doc, lo, hi)


def test_scanned_is_rows_returned_on_the_index(make_deltas):
    deltas = make_deltas("memory")
    for seq in range(1, 1001):
        deltas.insert_one(_row("a", seq))
    stats = {}
    rows = query_deltas(deltas, "a", 990, None, stats)
    assert len(rows) == 10 and stats["scanned"] == 10


def test_sqlite_scan_reports_the_table(make_deltas):
    deltas = make_deltas("sqlite")
    for seq in range(1, 31):
        deltas.insert_one(_row("a" if seq % 2 else "b", seq))
    stats = {}
    assert len(query_deltas(deltas, "a", 20, None, stats)) == 5
    assert stats["scanned"] == 30


def test_counters_count_reads_and_rows(make_deltas):
    deltas = make_deltas("memory")
    for seq in range(1, 11):
        deltas.insert_one(_row("a", seq))
    reads0 = counters.get("deltas.range_reads")
    rows0 = counters.get("deltas.range_rows")
    query_deltas(deltas, "a", 4, 8)
    query_deltas(deltas, "a", 10)
    assert counters.get("deltas.range_reads") - reads0 == 2
    assert counters.get("deltas.range_rows") - rows0 == 4


@pytest.mark.parametrize("backend", BACKENDS)
def test_range_read_needs_a_range_key(tmp_path, backend):
    if backend == "memory":
        plain = DatabaseManager().collection("plain")
    else:
        plain = SqliteDatabaseManager(
            str(tmp_path / "plain.sqlite")).collection("plain")
    with pytest.raises(TypeError):
        plain.find_range("a", 0)


def test_upsert_refused_on_an_indexed_collection():
    deltas = Collection(unique_key=delta_key, range_key=delta_key)
    deltas.insert_one(_row("a", 1))
    with pytest.raises(TypeError):
        deltas.upsert(lambda d: d["sequence_number"] == 1, _row("a", 1, "x"))
    assert [r["contents"] for r in query_deltas(deltas, "a")] == ["a:1"]


def test_late_join_reads_deltas_by_range_only():
    """A late join on a document of a few thousand persisted ops reads
    the delta store only through range reads of the index: no predicate
    scan runs, the rows read are the residue past the artifact (the join
    op), and every read looks at no more rows than it returns. The
    joined container equals a client that replays the whole tail."""
    from fluidframework_tpu.server.local_server import TpuLocalServer
    from fluidframework_tpu.telemetry import tracing
    from test_readpath import _flat, _fleet

    server = TpuLocalServer()
    loader, _, chans = _fleet(server, n_ops=2000)
    head = max(r["sequence_number"] for r in query_deltas(server.deltas,
                                                          "doc"))
    assert head > 2000

    def no_scan(predicate):
        raise AssertionError("a predicate scan of the delta store")

    server.deltas.find = no_scan
    reads0 = counters.get("deltas.range_reads")
    rows0 = counters.get("deltas.range_rows")
    adopted0 = counters.get("catchup.client.adopted")
    tracing.configure(sample=1)
    try:
        cd = loader.resolve("doc")
        spans = tracing.recorder.drain()
    finally:
        tracing.reset()
    assert counters.get("catchup.client.adopted") > adopted0
    (connect,) = [s for s in spans if s["name"] == "load.connect"]
    reads = [s["attrs"] for s in spans if s["name"] == "server.get_deltas"]
    residue = connect["attrs"]["residue_ops"]
    assert residue >= 1  # the join op, sequenced after the artifact
    assert 2 <= len(reads) == \
        counters.get("deltas.range_reads") - reads0 <= 3
    assert counters.get("deltas.range_rows") - rows0 == residue
    assert all(r["scanned"] == r["rows"] for r in reads)

    saved, server.catchup = server.catchup, None
    cr = loader.resolve("doc", client_details={"mode": "read"})
    server.catchup = saved
    td = cd.runtime.get_datastore("default").get_channel("text")
    tr = cr.runtime.get_datastore("default").get_channel("text")
    assert td.get_text() == tr.get_text() == chans[0].get_text()
    assert _flat(td) == _flat(tr)
    assert cd.protocol.sequence_number == cr.protocol.sequence_number
    assert cd.protocol.quorum.snapshot() == cr.protocol.quorum.snapshot()
