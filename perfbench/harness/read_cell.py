"""The read cell: late clients catching up on one hot document.

Set-up builds the document's history through the raw path: two writers'
wire boxcars on the raw topic of a ``TpuLocalServer(paged_lanes=True)``,
each sequenced by one pump, with one connected client summarizing every
``summary_every_ops`` ops as the reference summarizer does (maxOps).
The window is a closed loop: before each join the writers add
``tail_step_ops`` ops, so the tail past the latest summary walks through
every multiple of the step below ``summary_every_ops`` (from a seeded
phase: each seed sees the same tails in another order); then one late
client runs ``Loader.resolve`` through ``LocalDocumentServiceFactory``
on the default read path, and the time until its text is read is the
join's catch-up time.
"""

import time
from contextlib import contextmanager
from typing import List

import numpy as np

from . import reference, traffic
from .clock import Spans
from .result import Check

DOC = "doc0"


def tail_phase(seed: int, step: int, every: int) -> int:
    """Every seed sees the same tails in another phase: a seeded start
    past the last summary, a multiple of ``step`` below ``every``; each
    join then adds ``step`` ops."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 17])
    return step * int(rng.integers(0, every // step))


def _timed_service(factory, spans: Spans) -> None:
    """Wrap the driver instances each resolve creates, so the storage and
    delta-storage calls the Loader makes are harness spans."""
    create = factory.create_document_service

    def create_timed(document_id):
        service = create(document_id)
        to_storage = service.connect_to_storage
        to_deltas = service.connect_to_delta_storage

        def storage():
            s = to_storage()
            for m in ("get_catchup", "get_summary", "get_versions",
                      "get_catchup_artifact"):
                spans.wrap(s, m, "fetch")
            return s

        def deltas():
            d = to_deltas()
            spans.wrap(d, "get", "fetch")
            return d

        service.connect_to_storage = storage
        service.connect_to_delta_storage = deltas
        return service

    factory.create_document_service = create_timed


class ReadCell:
    def __init__(self, cell, seed: int, seconds: float, spans: Spans):
        from fluidframework_tpu.dds.sequence import SharedString
        from fluidframework_tpu.loader.container import Loader
        from fluidframework_tpu.loader.drivers.local import \
            LocalDocumentServiceFactory
        from fluidframework_tpu.server.local_server import TpuLocalServer

        self.cfg, self.mix = cell.config, cell.traffic
        self.seconds, self.spans = float(seconds), spans
        self.fleet = traffic.Fleet(self.cfg, self.mix, seed)
        self.step = int(self.mix["tail_step_ops"])
        self.phase = tail_phase(seed, self.step,
                                int(self.cfg["summary_every_ops"]))
        self.server = TpuLocalServer(paged_lanes=True)
        factory = LocalDocumentServiceFactory(self.server)
        _timed_service(factory, spans)
        self.loader = Loader(factory)
        self.summarizer = self.loader.create_detached(DOC)
        store = self.summarizer.runtime.create_datastore(traffic.STORE)
        self.summarizer.attach()
        store.create_channel(traffic.TEXT, SharedString.TYPE)
        self.ops: List[str] = []        # every writer op, in seq order
        self.op_seqs: List[int] = []    # the seq each was sequenced at
        self.since_summary = 0
        self.summaries = []             # (commit handle, ops applied)
        self.joins = []                 # (ops applied, text, bulk count)
        self.catchup_ms: List[float] = []
        for w in range(self.fleet.writers):
            self._send([self.fleet.join(0, w)])

    def _send(self, boxcars: List[bytes]) -> None:
        from fluidframework_tpu.server.local_server import RAW_TOPIC
        from fluidframework_tpu.server.wire import boxcar_from_wire

        # The core's raw topic carries boxcar objects (its copier stage
        # reads them), as a connection's submit would put them there.
        for raw in boxcars:
            self.server.log.send_to(RAW_TOPIC, 0, DOC, boxcar_from_wire(raw))
        self.server.pump()

    def write(self, n: int) -> None:
        """``n`` writer ops in boxcars of ``boxcar_ops``, writers taking
        turns; the summarizer summarizes at every ``summary_every_ops``."""
        every = int(self.cfg["summary_every_ops"])
        size = int(self.mix["boxcar_ops"])
        while n > 0:
            k = min(n, size, every - self.since_summary)
            head = self.fleet.head[0] = self.server.sequence_number(DOC)
            ops = [self.fleet.text_ops(np.zeros(1, np.int64))[0]
                   for _ in range(k)]
            w = (len(self.ops) // size) % self.fleet.writers
            self._send([self.fleet.boxcar(0, w, [(traffic.TEXT, o)
                                                  for o in ops])])
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
        with self.spans.span("join"):
            t0 = time.perf_counter()
            late = self.loader.resolve(DOC)
            channel = late.runtime.get_datastore(traffic.STORE).get_channel(
                traffic.TEXT)
            text = channel.get_text()
            self.catchup_ms.append((time.perf_counter() - t0) * 1e3)
        self.joins.append((len(self.ops), text, channel.bulk_catchup_count))
        late.close()
        self.server.pump()

    def window(self) -> dict:
        t0 = self.t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < self.seconds:
            with self.spans.span("write"):
                self.write(self.step)
            self.join()
            i += 1
        self.t1 = time.perf_counter()
        return {"seconds": self.t1 - t0, "joins": i}

    def check(self) -> List[Check]:
        """The late clients' text against the plain reference at each
        join's head; the server's text likewise; each summary taken in
        the window against the reference at the seq the summary states.
        The writers' ops are numbered by the sequencer in log order; the
        head after the window confirms that numbering."""
        import bisect
        import json

        first = self.joins[0][0] if self.joins else 0
        window_summaries = [(h, *self._summary(h))
                            for h, n in self.summaries if n >= first]
        at_seq = {seq: bisect.bisect_right(self.op_seqs, seq)
                  for _, seq, _ in window_summaries}
        want = ({n for n, _, _ in self.joins} | {len(self.ops)}
                | set(at_seq.values()))
        text_at = {0: ""}
        ref = reference.Text()
        for k, op in enumerate(self.ops, 1):
            ref.apply(json.loads(op))
            if k in want:
                text_at[k] = ref.text
        joins_bad = sum(1 for n, text, _ in self.joins if text != text_at[n])
        seq = self.server.sequencer()
        server_bad = int(seq.channel_text(DOC, traffic.STORE, traffic.TEXT)
                         != text_at[len(self.ops)])
        summary_bad = sum(1 for _, s, text in window_summaries
                          if text != text_at[at_seq[s]])
        numbering_bad = int(self.server.sequence_number(DOC)
                            < (self.op_seqs[-1] if self.op_seqs else 0))
        return [
            Check("joins_text_differs", joins_bad, 0),
            Check("server_text_differs", server_bad, 0),
            Check("summaries_differ", summary_bad, 0),
            Check("op_seqs_past_head", numbering_bad, 0),
            Check("summaries_checked", len(window_summaries), None),
            Check("joins_checked", len(self.joins), None),
        ]

    def _summary(self, handle: str):
        """(the seq a summary states, its text)."""
        import json

        tree = self.server.historian.read_summary(
            self.server.tenant_id, DOC, commit_sha=handle)
        seq = json.loads(tree.entries[".protocol"].content)["sequenceNumber"]
        node = tree.entries[".app"].entries[".dataStores"].entries[
            traffic.STORE].entries[".channels"].entries[traffic.TEXT]
        header = json.loads(node.entries["header"].content)
        segs = [s for i in range(header["chunkCount"])
                for s in json.loads(node.entries[f"body_{i}"].content)]
        return seq, "".join(s.get("text", "") for s in segs
                            if s.get("removedSeq") is None
                            and isinstance(s.get("text"), str))


class Run:
    def __init__(self, cell: ReadCell, window: dict, trace):
        self.spans, self.trace = cell.spans, trace
        self.t0, self.t1 = cell.t0, cell.t1
        self.window_s = window["seconds"]
        self.joins = cell.joins
        self.catchup_ms = cell.catchup_ms
        joins = [(a, b) for a, b in cell.spans.spans["join"]
                 if a >= cell.t0]
        self.fetch_ms = [cell.spans.total("fetch", a, b) * 1e3
                         for a, b in joins]


def run(cell, seed: int, seconds: float, traced: bool, clock, t_start: float,
        memory_peak) -> dict:
    t = time.perf_counter()
    rehearse(cell, seed, seconds)
    rehearsal_s = time.perf_counter() - t
    t = time.perf_counter()
    rc = build(cell, seed, seconds, Spans(annotate=traced),
               int(cell.traffic["warmup_joins"]))
    return _measure(rc, traced, clock, t_start, memory_peak, rehearsal_s,
                    time.perf_counter() - t)


def build(cell, seed: int, seconds: float, spans: Spans, joins: int):
    """The document's history, the seeded phase, then ``joins`` of the
    cell's own joins."""
    rc = ReadCell(cell, seed, seconds, spans)
    rc.write(int(cell.config["history_ops"]))
    rc.write(rc.phase)
    for _ in range(joins):
        rc.write(rc.step)
        rc.join()
    return rc


def rehearse(cell, seed: int, seconds: float) -> None:
    """A dress rehearsal on a throwaway server: the same history and
    ``rehearsal_joins`` more joins, so every program shape the window's
    growing document meets is compiled (or loaded) before the window, in
    this process."""
    import gc

    joins = int(cell.traffic.get("rehearsal_joins", 0))
    if joins:
        build(cell, seed, seconds, Spans(False),
              int(cell.traffic["warmup_joins"]) + joins)
        gc.collect()


def _diag(record: dict) -> None:
    import json
    import sys

    print("perfbench diag " + json.dumps(record), file=sys.stderr, flush=True)


@contextmanager
def _program_spans(rc):
    """The program's own tracing on for the body: every span recorded,
    and each ``with`` span also a ``fluid.<name>`` annotation on the
    profiler's host line. Afterwards the recorder is drained into the
    harness's spans, as ``fluid.<name>`` beside ``join`` on
    ``time.perf_counter``, and the program's tracing is off again."""
    from fluidframework_tpu.telemetry import compile_ledger, tracing

    compile_ledger.install_jax_listener()
    tracing.configure(sample=1, capacity=1 << 17, profiler=True)
    try:
        yield
    finally:
        dropped = tracing.recorder.dropped
        spans = tracing.recorder.drain()
        tracing.configure(sample=0, profiler=False)
    # A span's ts and dur are microseconds, ts on the wall clock.
    wall = time.time() - time.perf_counter()
    for s in spans:
        start = s["ts"] / 1e6 - wall
        rc.spans.spans["fluid." + s["name"]].append(
            (start, start + s["dur"] / 1e6))
    _diag({"program_spans": len(spans), "dropped": dropped})


def _measure(rc, traced, clock, t_start, memory_peak, rehearsal_s,
             warm_s) -> dict:
    import jax

    from . import trace as trace_mod

    rc.joins.clear()
    rc.catchup_ms.clear()
    compiles0 = clock.compiles
    captured = {}
    setup_s = time.perf_counter() - t_start
    if traced:
        with _program_spans(rc), trace_mod.capture(captured):
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                window = rc.window()
    else:
        window = rc.window()
    peak = memory_peak()
    reduced = trace_mod.reduce(captured["planes"], {}) \
        if captured.get("planes") else None
    run_ = Run(rc, window, reduced)
    _diag({"rehearsal_s": rehearsal_s, "history_and_warm_up_s": warm_s,
           "joins": window["joins"],
           "window_compiles": clock.compiles - compiles0,
           "window_compiled": sorted(set(clock.names[compiles0:])),
           "compile_s": clock.seconds, "cache_hits": clock.hits,
           "cache_misses": clock.misses, "ops": len(rc.ops),
           "catchup_ms": rc.catchup_ms[:40], "fetch_ms": run_.fetch_ms[:40],
           "bulk_catchups": sum(1 for _, _, b in rc.joins if b)})
    run_.compiles = clock.compiles - compiles0
    checks = rc.check()
    e2e = {"setup_s": setup_s,
           "catchup_ms_p50": float(np.percentile(rc.catchup_ms, 50))
           if rc.catchup_ms else None}
    bad = checks[0].value
    return {"end_to_end": e2e, "run": run_, "trace": reduced,
            "checks": checks, "memory_peak_bytes": peak,
            "attempted": len(rc.joins), "failed": bad}
