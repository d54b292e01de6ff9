"""The matrix cell: late clients opening one storm-edited SharedMatrix.

Set-up creates the document as a spreadsheet's creator does: one
SharedMatrix whose ``rows`` x ``cols`` are inserted before attach. Then
it builds the history through the raw path, as the read cell does (two
writers' wire boxcars of ``matrix_traffic`` storm ops on the raw topic of
a ``TpuLocalServer``, one connected client summarizing every
``summary_every_ops`` ops). The window is a closed loop: before each
join the writers add ``tail_step_ops`` ops in one boxcar, so the tail
past the latest summary walks every multiple of the step from a seeded
phase (read_cell.tail_phase); then one late client runs
``Loader.resolve`` on the default read path, and the time until it has
read the sheet's dimensions and a ``viewport`` of cells at the active
cell (a spreadsheet's first screen) is the join's catch-up time.

Outside the timer each late client's grid is reduced to its dimensions
and its sorted non-empty (row, col, value) triples, hashed, and
``check()`` compares that, the viewport, the server's materialized grid
and each window summary with the plain reference (matrix_reference.py),
and the creator's row and column ids with those the reference derives.
"""

import bisect
import json
import time
from collections import defaultdict
from typing import List

import numpy as np

from . import matrix_reference, matrix_traffic, traffic
from .clock import Spans
from .read_cell import _diag, _measure, _timed_service, tail_phase
from .result import Check

DOC = "doc0"
GRID = matrix_traffic.GRID


def _ids(vector) -> List[str]:
    return ["%d.%d.%d" % i for i in vector.ids_in_order()]


def viewport(grid, top: int, left: int, n_rows: int, n_cols: int):
    """The late client's cells of the window at (top, left), clipped at
    the edges: read off its axes' id order and its cell map."""
    rows = _ids(grid.rows)[top:top + n_rows]
    cols = _ids(grid.cols)[left:left + n_cols]
    return [[grid.cells.get(r + "|" + c) for c in cols] for r in rows]


def client_digest(grid) -> str:
    """A late client's grid, reduced as the reference reduces its own."""
    rows, cols = _ids(grid.rows), _ids(grid.cols)
    return matrix_reference.digest(
        len(rows), len(cols),
        matrix_reference.grid_triples(rows, cols, grid.cells))


class MatrixCell:
    def __init__(self, cell, seed: int, seconds: float, spans: Spans,
                 keep: bool = True):
        from fluidframework_tpu.dds.matrix import SharedMatrix
        from fluidframework_tpu.loader.container import Loader
        from fluidframework_tpu.loader.drivers.local import \
            LocalDocumentServiceFactory
        from fluidframework_tpu.server.local_server import TpuLocalServer

        self.cfg, self.mix = cell.config, cell.traffic
        self.seconds, self.spans, self.keep = float(seconds), spans, keep
        self.fleet = traffic.Fleet(self.cfg, self.mix, seed)
        self.step = int(self.mix["tail_step_ops"])
        self.phase = tail_phase(seed, self.step,
                                int(self.cfg["summary_every_ops"]))
        self.viewport = tuple(int(v) for v in self.mix["viewport"])
        self.server = TpuLocalServer(paged_lanes=bool(self.cfg["paged_lanes"]))
        factory = LocalDocumentServiceFactory(self.server)
        _timed_service(factory, spans)
        self.loader = Loader(factory)
        self.summarizer = self.loader.create_detached(DOC)
        store = self.summarizer.runtime.create_datastore(traffic.STORE)
        grid = store.create_channel(GRID, SharedMatrix.TYPE)
        # The creator's run ids come from the seed too: one seed, one sheet.
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 13])
        nonces = [int(n) for n in rng.integers(1, 2**48, 2)]
        grid.rows.nonce, grid.cols.nonce = nonces
        n_rows, n_cols = int(self.cfg["rows"]), int(self.cfg["cols"])
        grid.insert_rows(0, n_rows)
        grid.insert_cols(0, n_cols)
        self.summarizer.attach()
        # The creator's first insert on an axis is its run 1 of the
        # nonce set above (PermutationVector), so the reference and the
        # writers start from ids derived here; the program's own are
        # checked against them, not taken as input.
        self.start = (matrix_reference.run_ids([nonces[0], 1, 0, n_rows]),
                      matrix_reference.run_ids([nonces[1], 1, 0, n_cols]))
        self.creator_ids_bad = int(
            (_ids(grid.rows), _ids(grid.cols)) != self.start)
        self.storm = matrix_traffic.Storm(*self.start, seed,
                                          self.fleet.writers)
        self.ops: List[str] = []        # every writer op, in seq order
        self.op_seqs: List[int] = []    # the seq each was sequenced at
        self.since_summary = 0
        self.summaries = []             # (commit handle, ops applied)
        # (ops applied, (digest, dims, origin, viewport), adopted)
        self.joins = []
        self.catchup_ms: List[float] = []
        for w in range(self.fleet.writers):
            self._send([self.fleet.join(0, w)])

    def _send(self, boxcars: List[bytes]) -> None:
        from fluidframework_tpu.server.local_server import RAW_TOPIC
        from fluidframework_tpu.server.wire import boxcar_from_wire

        for raw in boxcars:
            self.server.log.send_to(RAW_TOPIC, 0, DOC, boxcar_from_wire(raw))
        self.server.pump()

    def write(self, n: int) -> None:
        """``n`` storm ops in boxcars of ``boxcar_ops``, writers taking
        turns; the summarizer summarizes at every ``summary_every_ops``."""
        every = int(self.cfg["summary_every_ops"])
        size = int(self.mix["boxcar_ops"])
        while n > 0:
            k = min(n, size, every - self.since_summary)
            head = self.fleet.head[0] = self.server.sequence_number(DOC)
            w = (len(self.ops) // size) % self.fleet.writers
            ops = self.storm.ops(k, w)
            self._send([self.fleet.boxcar(0, w, [(GRID, o) for o in ops])])
            self.ops += ops
            self.op_seqs += range(head + 1, head + k + 1)
            n -= k
            self.since_summary += k
            if self.since_summary == every:
                handle = self.summarizer.summarize()
                self.server.pump()
                self.summaries.append((handle, len(self.ops)))
                self.since_summary = 0

    def join(self) -> None:
        from fluidframework_tpu.telemetry import counters

        n_rows, n_cols = self.viewport
        top = max(0, min(self.storm.active[0],
                         len(self.storm.rows) - n_rows))
        left = max(0, min(self.storm.active[1],
                          len(self.storm.cols) - n_cols))
        adopted = counters.get("catchup.client.adopted")
        with self.spans.span("join"):
            t0 = time.perf_counter()
            late = self.loader.resolve(DOC)
            grid = late.runtime.get_datastore(traffic.STORE).get_channel(
                GRID)
            dims = (grid.row_count, grid.col_count)
            view = viewport(grid, top, left, n_rows, n_cols)
            self.catchup_ms.append((time.perf_counter() - t0) * 1e3)
        adopted = counters.get("catchup.client.adopted") > adopted
        if self.keep:
            self.joins.append((len(self.ops),
                               (client_digest(grid), dims, (top, left),
                                view), adopted))
        late.close()
        self.server.pump()

    def window(self) -> dict:
        from fluidframework_tpu.telemetry import counters

        self.counters0 = counters.snapshot()
        t0 = self.t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < self.seconds:
            with self.spans.span("write"):
                self.write(self.step)
            self.join()
            i += 1
        self.t1 = time.perf_counter()
        self.counters1 = counters.snapshot()
        return {"seconds": self.t1 - t0, "joins": i}

    def check(self) -> List[Check]:
        """The late clients' grids and viewports against the plain
        reference at each join's head; the server's grid likewise at the
        window's end; each summary taken in the window against the
        reference at the seq the summary states. The writers' ops are
        numbered by the sequencer in log order; the head after the
        window confirms that numbering."""
        first = self.joins[0][0] if self.joins else 0
        window_summaries = [(h, *self._summary(h))
                            for h, n in self.summaries if n >= first]
        at_seq = {seq: bisect.bisect_right(self.op_seqs, seq)
                  for _, seq, _ in window_summaries}
        want = ({n for n, _, _ in self.joins} | {len(self.ops)}
                | set(at_seq.values()))
        joins_at = defaultdict(list)
        for n, seen, _ in self.joins:
            joins_at[n].append(seen)
        digest_at = {}
        joins_bad = 0
        ref = matrix_reference.Matrix(*self.start)
        for k in range(len(self.ops) + 1):
            if k:
                ref.apply(json.loads(self.ops[k - 1]))
            if k not in want:
                continue
            digest_at[k] = ref.digest()
            for digest, dims, (top, left), view in joins_at[k]:
                joins_bad += int(
                    digest != digest_at[k]
                    or dims != (len(ref.rows), len(ref.cols))
                    or view != ref.window(top, left, *self.viewport))
        seq = self.server.sequencer()
        server_bad = int(self._server_digest(seq)
                         != digest_at[len(self.ops)])
        summary_bad = sum(1 for _, s, digest in window_summaries
                          if digest != digest_at[at_seq[s]])
        numbering_bad = int(self.server.sequence_number(DOC)
                            < (self.op_seqs[-1] if self.op_seqs else 0))
        return [
            Check("creator_ids_differ", self.creator_ids_bad, 0),
            Check("joins_grid_differs", joins_bad, 0),
            Check("server_grid_differs", server_bad, 0),
            Check("summaries_differ", summary_bad, 0),
            Check("op_seqs_past_head", numbering_bad, 0),
            Check("summaries_checked", len(window_summaries), None),
            Check("joins_checked", len(self.joins), None),
            Check("joins_adopted", sum(1 for *_, a in self.joins if a),
                  None),
        ]

    def _server_digest(self, seq) -> str:
        grid = seq.channel_matrix(DOC, traffic.STORE, GRID)
        if grid is None:
            return ""
        return matrix_reference.digest(
            len(grid), len(grid[0]) if grid else 0,
            [(i, j, v) for i, row in enumerate(grid)
             for j, v in enumerate(row) if v is not None])

    def _summary(self, handle: str):
        """(the seq a summary states, its grid's digest)."""
        tree = self.server.historian.read_summary(
            self.server.tenant_id, DOC, commit_sha=handle)
        seq = json.loads(tree.entries[".protocol"].content)["sequenceNumber"]
        node = tree.entries[".app"].entries[".dataStores"].entries[
            traffic.STORE].entries[".channels"].entries[GRID]

        def axis(name):
            snap = json.loads(node.entries[name].content)
            return [i for s in snap["segments"]
                    if s.get("removedSeq") is None
                    for i in matrix_reference.run_ids(s["text"]["run"])]

        rows, cols = axis("rows"), axis("cols")
        cells = json.loads(node.entries["cells"].content)
        return seq, matrix_reference.digest(
            len(rows), len(cols),
            matrix_reference.grid_triples(rows, cols, cells))

    def lane_report(self) -> dict:
        """The cell lane's standing on the server: resident or opaque,
        its bucket and live keys, and whether the read tier takes it."""
        seq = self.server.sequencer()
        lww = seq.lww
        key = next((k for k in lww.where if k[0] == DOC), None)
        keys = None
        if key is not None:
            b, lane = lww.where[key]
            keys = int(np.count_nonzero(
                np.asarray(lww.buckets[b].state.key[lane]) >= 0))
        _, unsupported = seq.catchup_docs_supported()
        return {"lww_opaque": len(lww.opaque),
                "overflow_drops": lww.overflow_drops,
                "cell_lane_capacity": None if key is None
                else lww.buckets[lww.where[key][0]].capacity,
                "cell_keys": keys,
                "read_tier_supported": DOC not in unsupported}


def run(cell, seed: int, seconds: float, traced: bool, clock, t_start: float,
        memory_peak) -> dict:
    t = time.perf_counter()
    rehearse(cell, seed, seconds)
    rehearsal_s = time.perf_counter() - t
    t = time.perf_counter()
    rc = build(cell, seed, seconds, Spans(annotate=traced),
               int(cell.traffic["warmup_joins"]))
    report = rc.lane_report()
    out = _measure(rc, traced, clock, t_start, memory_peak, rehearsal_s,
                   time.perf_counter() - t)
    run_ = out["run"]
    run_.counters0, run_.counters1 = rc.counters0, rc.counters1
    report.update(joins=len(rc.joins),
                  adoptions=sum(1 for *_, a in rc.joins if a))
    if out["trace"]:
        from .roofline import lww_apply_share

        report["lww_apply_hbm_share"] = lww_apply_share(run_)
    _diag(report)
    return out


def build(cell, seed: int, seconds: float, spans: Spans, joins: int,
          keep: bool = True):
    """The sheet's history, the seeded phase, then ``joins`` of the
    cell's own joins."""
    rc = MatrixCell(cell, seed, seconds, spans, keep)
    rc.write(int(cell.config["history_ops"]))
    rc.write(rc.phase)
    for _ in range(joins):
        rc.write(rc.step)
        rc.join()
    return rc


def rehearse(cell, seed: int, seconds: float) -> None:
    """A dress rehearsal on a throwaway server (read_cell.rehearse): the
    same history and ``rehearsal_joins`` more joins, so every program
    shape the window's growing sheet meets is compiled (or loaded)
    before the window, in this process."""
    import gc

    joins = int(cell.traffic.get("rehearsal_joins", 0))
    if joins:
        build(cell, seed, seconds, Spans(False),
              int(cell.traffic["warmup_joins"]) + joins, keep=False)
        gc.collect()
