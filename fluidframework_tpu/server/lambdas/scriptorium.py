"""Scriptorium: persists sequenced deltas (reference scriptorium/lambda.ts:
16-103 — batched Mongo insertMany, idempotent on duplicate keys, traces
stripped before persisting)."""

from __future__ import annotations

from dataclasses import asdict
from typing import List, Optional

from ...protocol.messages import SequencedDocumentMessage
from ...telemetry.counters import increment
from ..database import Collection
from ..log import QueuedMessage
from .base import IPartitionLambda, LambdaContext


class ScriptoriumLambda(IPartitionLambda):
    def __init__(self, context: LambdaContext, deltas: Collection):
        self.context = context
        self.deltas = deltas

    def handler(self, message: QueuedMessage) -> None:
        value = message.value
        if hasattr(value, "messages"):
            # A SequencedWindow (tpu_sequencer fast path): ONE log record
            # per flush; persist every admitted message it carries — the
            # reference's insertMany batch, naturally window-sized.
            for doc_id, sequenced in value.messages():
                self._persist(doc_id, sequenced)
            self.context.checkpoint(message.offset)
            return
        doc_id, sequenced = value
        self._persist(doc_id, sequenced)
        self.context.checkpoint(message.offset)

    def _persist(self, doc_id: str,
                 sequenced: SequencedDocumentMessage) -> None:
        record = asdict(sequenced)
        record["traces"] = []  # strip latency traces before persisting
        record["documentId"] = doc_id
        # The in-memory collection makes the reference's batched async
        # insertMany a synchronous insert; duplicates (replay) are ignored.
        self.deltas.insert_one(record)


def delta_key(doc: dict):
    """The delta store's unique key, and its range key: (partition,
    order) for `Collection.find_range`."""
    return (doc["documentId"], doc["sequence_number"])


def query_deltas(deltas: Collection, document_id: str, from_seq: int = 0,
                 to_seq=None, stats: Optional[dict] = None) -> List[dict]:
    """Catch-up range query over the delta store: rows with
    from_seq < seq <= to_seq, ordered (alfred's delta REST semantics).
    One range read of the store's (documentId, sequence_number) index:
    its cost is the rows it returns. stats, if given, receives "scanned"
    (the rows the store looked at)."""
    rows = deltas.find_range(document_id, from_seq, to_seq, stats)
    increment("deltas.range_reads")
    increment("deltas.range_rows", len(rows))
    return rows
