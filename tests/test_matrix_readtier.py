"""SharedMatrix on the paged served path and the read tier: the cell lane's
key-capacity ladder (promotion keeps the lane resident, exhaustion
degrades it to opaque), the server's materialized grid, and late joins
that adopt the read tier's matrix artifact — adoption at the artifact's
seq plus replay of the residue equals full replay and the plain
reference (perfbench/harness/matrix_reference.py), whatever the residue,
across summaries, beside a SharedString, and with a fallback to tail
replay where the cell lane went opaque.

Traffic is the benchmark's seeded storm (perfbench/harness/
matrix_traffic.py) as raw wire boxcars, checked against the reference."""

import json

import pytest

from fluidframework_tpu.dds.matrix import SharedMatrix
from fluidframework_tpu.dds.sequence import SharedString
from fluidframework_tpu.loader.container import Loader
from fluidframework_tpu.loader.drivers.local import (
    LocalDocumentServiceFactory,
)
from fluidframework_tpu.server.local_server import RAW_TOPIC, TpuLocalServer
from fluidframework_tpu.server.tpu_sequencer import (
    MATRIX_CELLS_SUFFIX,
    LwwLaneStore,
)
from fluidframework_tpu.server.wire import boxcar_from_wire
from fluidframework_tpu.telemetry import counters
from perfbench.harness import matrix_reference, matrix_traffic, traffic

DOC = "doc0"
STORE = traffic.STORE
GRID = matrix_traffic.GRID
CELLS = (DOC, STORE, GRID + MATRIX_CELLS_SUFFIX)


def _ids(vector):
    return ["%d.%d.%d" % i for i in vector.ids_in_order()]


def _digest(grid) -> str:
    rows, cols = _ids(grid.rows), _ids(grid.cols)
    return matrix_reference.digest(
        len(rows), len(cols),
        matrix_reference.grid_triples(rows, cols, grid.cells))


def _window(grid, row, col, n_rows, n_cols):
    cols = _ids(grid.cols)[col:col + n_cols]
    return [[grid.cells.get(r + "|" + c) for c in cols]
            for r in _ids(grid.rows)[row:row + n_rows]]


def _server_digest(server) -> str:
    grid = server.sequencer().channel_matrix(DOC, STORE, GRID)
    return matrix_reference.digest(
        len(grid), len(grid[0]) if grid else 0,
        [(i, j, v) for i, row in enumerate(grid)
         for j, v in enumerate(row) if v is not None])


class Sheet:
    """One SharedMatrix document on a TpuLocalServer: a
    creator inserts the rows and columns before attach, two raw writers
    send storm ops, and the reference applies every op in seq order."""

    def __init__(self, rows=12, cols=10, seed=5, capacities=None,
                 text=False):
        self.server = TpuLocalServer()
        if capacities is not None:
            self.server.sequencer().lww = LwwLaneStore(capacities=capacities)
        self.loader = Loader(LocalDocumentServiceFactory(self.server))
        self.creator = self.loader.create_detached(DOC)
        store = self.creator.runtime.create_datastore(STORE)
        self.text = store.create_channel("text", SharedString.TYPE) \
            if text else None
        grid = store.create_channel(GRID, SharedMatrix.TYPE)
        grid.rows.nonce, grid.cols.nonce = 1000 + seed, 2000 + seed
        grid.insert_rows(0, rows)
        grid.insert_cols(0, cols)
        self.creator.attach()
        # The creator's first insert on an axis is its run 1: the
        # reference derives those ids from the nonces, and the program's
        # must match them.
        self.ref = matrix_reference.Matrix(
            matrix_reference.run_ids([1000 + seed, 1, 0, rows]),
            matrix_reference.run_ids([2000 + seed, 1, 0, cols]))
        assert (_ids(grid.rows), _ids(grid.cols)) == \
            (self.ref.rows, self.ref.cols)
        self.fleet = traffic.Fleet({"documents": 1}, {"writers_per_doc": 2},
                                   seed)
        self.storm = matrix_traffic.Storm(self.ref.rows, self.ref.cols, seed,
                                          2)
        self.sent = 0
        self.axis_ops = []  # (axis, merge op type) of each axis op sent
        for w in range(2):
            self._send(self.fleet.join(0, w))

    def _send(self, raw: bytes) -> None:
        self.server.log.send_to(RAW_TOPIC, 0, DOC, boxcar_from_wire(raw))
        self.server.pump()

    def send_ops(self, ops) -> None:
        self.fleet.head[0] = self.server.sequence_number(DOC)
        w = self.sent % 2
        self.sent += 1
        self._send(self.fleet.boxcar(0, w, [(GRID, o) for o in ops]))
        for o in ops:
            op = json.loads(o)
            self.ref.apply(op)
            if op["target"] != "cell":
                self.axis_ops.append((op["target"], op["op"]["type"]))

    def write(self, n: int, size: int = 25) -> None:
        while n > 0:
            k = min(n, size)
            self.send_ops(self.storm.ops(k, self.sent % 2))
            n -= k

    def set_cells(self, n: int) -> None:
        """``n`` writes to distinct cells: the cell lane's key count grows
        by ``n``."""
        cols = len(self.ref.cols)
        keys = [self.ref.rows[i // cols] + "|" + self.ref.cols[i % cols]
                for i in range(n)]
        for at in range(0, n, 25):
            self.send_ops([json.dumps({"target": "cell", "key": k,
                                       "value": f"v{at + i}"})
                           for i, k in enumerate(keys[at:at + 25])])

    def resolve(self, replay: bool = False):
        """A late client's matrix; ``replay`` takes the read tier away,
        so the client replays the tail past its summary."""
        if replay:
            saved, self.server.catchup = self.server.catchup, None
        try:
            late = self.loader.resolve(DOC)
        finally:
            if replay:
                self.server.catchup = saved
        return late, late.runtime.get_datastore(STORE).get_channel(GRID)


def _adopted(fn):
    before = counters.get("catchup.client.adopted")
    out = fn()
    return out, counters.get("catchup.client.adopted") - before


@pytest.mark.parametrize("keys, bucket", [(6, 0), (20, 1), (100, 2),
                                          (200, None)])
def test_cell_lane_promotes_then_degrades_past_the_top(keys, bucket):
    """Ladder (8, 32, 128): a lane promoted once or twice stays resident
    and equals the reference; one past the top goes opaque with one
    overflow drop, and the server no longer materializes its cells."""
    promotions = counters.get("lww.promotions")
    drops = counters.get("lww.overflow_drops")
    sheet = Sheet(rows=20, cols=12, capacities=(8, 32, 128))
    sheet.set_cells(keys)
    lww = sheet.server.sequencer().lww
    if bucket is None:
        assert CELLS in lww.opaque and CELLS not in lww.where
        assert lww.overflow_drops == 1
        assert counters.get("lww.overflow_drops") - drops == 1
        assert _server_digest(sheet.server) != sheet.ref.digest()
        return
    assert not lww.opaque and lww.overflow_drops == 0
    assert lww.where[CELLS][0] == bucket
    assert counters.get("lww.promotions") - promotions == bucket
    assert _server_digest(sheet.server) == sheet.ref.digest()


def test_ladder_holds_a_full_sheet_and_sizes_lanes_per_capacity():
    from fluidframework_tpu.server.tpu_sequencer import (LWW_CAPACITIES,
                                                         lww_lanes_for)
    store = LwwLaneStore()
    assert store.capacities == LWW_CAPACITIES
    assert store.capacities[-1] >= 1000 * 1000
    assert [b.lanes for b in store.buckets] == \
        [lww_lanes_for(c) for c in LWW_CAPACITIES]
    assert store.buckets[0].lanes == 8 and store.buckets[-1].lanes == 1
    # The big tables are built on first use only.
    assert all(b._state is None for b in store.buckets)


@pytest.mark.parametrize("seed", [3, 17, 2**31 + 9])
def test_server_grid_equals_reference_after_storm(seed):
    sheet = Sheet(rows=14, cols=11, seed=seed)
    sheet.write(600)
    removes = {t for t, kind in sheet.axis_ops if kind == 1}
    assert removes == {"rows", "cols"}
    assert _server_digest(sheet.server) == sheet.ref.digest()
    grid = sheet.server.sequencer().channel_matrix(DOC, STORE, GRID)
    assert grid == sheet.ref.window(0, 0, len(sheet.ref.rows),
                                    len(sheet.ref.cols))


@pytest.mark.parametrize("residue, summary", [
    (0, None), (1, None), (25, None), (25, "before_artifact"),
    (25, "in_residue")])
def test_late_join_adopts_and_equals_replay(residue, summary):
    """Adoption at the artifact's seq plus replay of the residue equals
    full replay and the reference at head, with a summary before the
    artifact or inside the residue (the artifact names the summary it
    was built on, and the client loads that one)."""
    sheet = Sheet(rows=16, cols=12, seed=7)
    sheet.write(300)
    if summary == "before_artifact":
        sheet.creator.summarize()
        sheet.server.pump()
        sheet.write(100)
    sheet.server.refresh_catchup()
    published = sheet.server.catchup.peek_seq(sheet.server.tenant_id, DOC)
    assert published == sheet.server.sequence_number(DOC)
    sheet.write(residue)
    if summary == "in_residue":
        sheet.creator.summarize()
        sheet.server.pump()
    # Serve the artifact as published: no refresh on read.
    sheet.server._refresh_catchup = lambda only_docs: {}
    (late, grid), adopted = _adopted(sheet.resolve)
    assert adopted == 1
    _, replayed = sheet.resolve(replay=True)
    assert _digest(grid) == _digest(replayed) == sheet.ref.digest()
    rows, cols = len(sheet.ref.rows), len(sheet.ref.cols)
    assert (grid.row_count, grid.col_count) == (rows, cols)
    assert _window(grid, 3, 2, 9, 7) == _window(replayed, 3, 2, 9, 7) \
        == sheet.ref.window(3, 2, 9, 7)
    # The adopted replica keeps converging with later edits.
    sheet.write(50)
    assert _digest(grid) == sheet.ref.digest()


def test_mixed_string_and_matrix_document_adopts_both():
    sheet = Sheet(rows=10, cols=9, seed=21, text=True)
    for i in range(40):
        sheet.text.insert_text(0, f"<{i}>")
        sheet.write(10)
    sheet.server.pump()
    supported = sheet.server.sequencer().catchup_docs_supported()
    assert DOC not in supported[1]
    (late, grid), adopted = _adopted(sheet.resolve)
    assert adopted == 1
    text = late.runtime.get_datastore(STORE).get_channel("text")
    assert text.get_text() == sheet.text.get_text()
    assert _digest(grid) == sheet.ref.digest()


def test_opaque_cell_lane_excludes_document_and_falls_back():
    sheet = Sheet(rows=12, cols=10, seed=4, capacities=(4, 8))
    sheet.set_cells(30)
    sheet.write(100)
    seq = sheet.server.sequencer()
    assert CELLS in seq.lww.opaque
    assert DOC in seq.catchup_docs_supported()[1]
    assert sheet.server.refresh_catchup()["published"] == 0
    (late, grid), adopted = _adopted(sheet.resolve)
    assert adopted == 0
    assert _digest(grid) == sheet.ref.digest()
