"""Sustained-typing serving decay probe: does ingest throughput stay
flat as documents age?

Drives waves of same-document typing boxcars through the REAL
TpuSequencerLambda raw fast path and reports per-wave rates. Merge lanes
live in the page pool (docs/paged_memory.md): a document grows by
appending pages and the compaction tick defrags the busiest documents
under a per-tick budget, so steady-state throughput should stay flat,
with a bounded maintenance wave at each compaction tick.

    python -m fluidframework_tpu.server.decay_probe               # quick
    python -m fluidframework_tpu.server.decay_probe --docs 256 \
        --ops 16 --waves 40

Prints one JSON line: fast-wave median rate, maintenance-wave count, sustained
rate, and lane-health counters. Exit nonzero if the LAST quartile of
fast waves is >2x slower than the first (decay = the regression this
tool exists to catch).

Reference analog: the deli lambda's steady-state throughput under
sustained per-document traffic (deli/lambda.ts:142 ticket loop, whose
cost does not grow with document age because the TS merge-tree zamboni
packs acked segments, mergeTree.ts:1289)."""

from __future__ import annotations

import argparse
import json
import random
import sys
import time


def run(docs: int, ops: int, waves: int) -> dict:
    from fluidframework_tpu.mergetree.client import OP_INSERT
    from fluidframework_tpu.protocol.messages import (Boxcar,
                                                      DocumentMessage,
                                                      MessageType)
    from fluidframework_tpu.server.log import QueuedMessage
    from fluidframework_tpu.server.tpu_sequencer import TpuSequencerLambda
    from fluidframework_tpu.server.wire import boxcar_to_wire

    class Ctx:
        def checkpoint(self, *_):
            pass

        def error(self, err, restart=False):
            raise err

    def build_wave(wave: int):
        rng = random.Random(17 + wave)
        out = []
        base_csn = wave * ops
        for d in range(docs):
            contents = []
            if wave == 0:
                contents.append(DocumentMessage(
                    client_sequence_number=0,
                    reference_sequence_number=-1,
                    type=MessageType.CLIENT_JOIN,
                    data=json.dumps({"clientId": f"c{d}", "detail": {}})))
            for i in range(ops):
                n = rng.randrange(1, 4)
                contents.append(DocumentMessage(
                    client_sequence_number=base_csn + i + 1,
                    reference_sequence_number=base_csn,
                    type=MessageType.OPERATION,
                    contents={"address": "s", "contents": {
                        "address": "t", "contents": {
                            "type": OP_INSERT, "pos1": 0,
                            "seg": {"text": "x" * n}}}}))
            out.append(QueuedMessage(
                topic="rawdeltas", partition=0, offset=wave * docs + d,
                key=f"d{d}",
                value=boxcar_to_wire(Boxcar(
                    tenant_id="b", document_id=f"d{d}", client_id=f"c{d}",
                    contents=contents))))
        return out

    lam = TpuSequencerLambda(Ctx(), emit=lambda *a: None,
                             nack=lambda *a: None, client_timeout_s=0.0)
    lam.emit_window = lambda w: None
    lam.pipelined = True
    if lam._pump is None:
        raise RuntimeError("native wirepump unavailable")

    from fluidframework_tpu.telemetry import counters

    rates = []
    prebuilt = [build_wave(w) for w in range(waves)]
    for w, msgs in enumerate(prebuilt):
        t0 = time.perf_counter()
        for qm in msgs:
            lam.handler_raw(qm)
        lam.flush()
        lam.drain()
        rates.append(docs * ops / (time.perf_counter() - t0))
        # Live gauge per wave: the monitor/health surface sees sustained-
        # typing throughput (and its decay) while the probe runs, instead
        # of the reading living only in this process's stdout.
        counters.gauge("decay_probe.wave_ops_s", rates[-1])
        counters.increment("decay_probe.waves")
    # Warmup (compiles, first page growth) = first quarter; classify the
    # rest into fast waves vs maintenance (compaction) waves by median gap.
    tail = rates[waves // 4:]
    med = sorted(tail)[len(tail) // 2]
    fast = [r for r in tail if r >= med / 3]
    slow = [r for r in tail if r < med / 3]
    total_ops = docs * ops * len(tail)
    sustained = total_ops / sum(docs * ops / r for r in tail)
    q = max(1, len(fast) // 4)
    first_q = sorted(fast[:q])[q // 2]
    last_q = sorted(fast[-q:])[q // 2]
    import jax
    decayed = bool(last_q * 2 < first_q)
    # Final verdict + sustained rate into the process counters: a monitor
    # watching this process (or a bench run embedding the probe) exports
    # them via /health and /metrics.prom.
    counters.gauge("decay_probe.sustained_ops_s", sustained)
    counters.gauge("decay_probe.decayed", 1.0 if decayed else 0.0)
    return {
        "backend": jax.default_backend(),
        "docs": docs, "ops_per_wave": ops, "waves": waves,
        "fast_wave_median_ops_per_sec": round(med, 1),
        "fast_wave_first_quartile_median": round(first_q, 1),
        "fast_wave_last_quartile_median": round(last_q, 1),
        "maintenance_waves": len(slow),
        "sustained_ops_per_sec": round(sustained, 1),
        "page_compactions": lam.merge.page_compactions,
        "paged_rescues": lam.merge.paged_rescues,
        "payload_compactions": lam.merge.payload_compactions,
        "blocks_aged": lam.merge.blocks_aged,
        "overflow_drops": lam.merge.overflow_drops,
        "decayed": decayed,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--docs", type=int, default=256)
    ap.add_argument("--ops", type=int, default=16)
    ap.add_argument("--waves", type=int, default=40)
    args = ap.parse_args()
    out = run(args.docs, args.ops, args.waves)
    print(json.dumps(out))
    return 1 if out["decayed"] else 0


if __name__ == "__main__":
    sys.exit(main())
