"""In-memory database collections (reference services-core ICollection /
IDatabaseManager over MongoDB). Scriptorium's delta store and deli/scribe
checkpoints live here; inserts are idempotent on unique keys the way the
reference relies on dup-key 11000 being ignored on replay
(scriptorium/lambda.ts:92-99)."""

from __future__ import annotations

import bisect
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple


class Collection:
    """range_key, when given, maps a row to (partition, order) and keeps a
    per-partition index sorted by order, so `find_range` bisects and
    copies only the rows it returns (the delta store's
    (documentId, sequence_number) index)."""

    def __init__(self, unique_key: Optional[Callable[[dict], Any]] = None,
                 range_key: Optional[Callable[[dict], Tuple[Any, Any]]]
                 = None):
        self._docs: List[dict] = []
        self._unique: Dict[Any, int] = {}
        self._unique_key = unique_key
        self._range_key = range_key
        # partition -> (orders ascending, the stored rows in that order)
        self._ranges: Dict[Any, Tuple[List[Any], List[dict]]] = {}
        self._lock = threading.Lock()

    def insert_one(self, doc: dict) -> bool:
        """False if a doc with the same unique key exists (idempotent replay)."""
        with self._lock:
            if self._unique_key is not None:
                key = self._unique_key(doc)
                if key in self._unique:
                    return False
                self._unique[key] = len(self._docs)
            row = dict(doc)
            self._docs.append(row)
            if self._range_key is not None:
                partition, order = self._range_key(row)
                orders, rows = self._ranges.setdefault(partition, ([], []))
                if not orders or order >= orders[-1]:
                    orders.append(order)
                    rows.append(row)
                else:
                    # Below the partition's max: an at-least-once replay.
                    # bisect_right keeps equal orders in insertion order.
                    i = bisect.bisect_right(orders, order)
                    orders.insert(i, order)
                    rows.insert(i, row)
            return True

    def insert_many(self, docs: List[dict]) -> int:
        return sum(1 for d in docs if self.insert_one(d))

    def find(self, predicate: Callable[[dict], bool]) -> List[dict]:
        with self._lock:
            return [dict(d) for d in self._docs if predicate(d)]

    def find_one(self, predicate: Callable[[dict], bool]) -> Optional[dict]:
        with self._lock:
            for d in self._docs:
                if predicate(d):
                    return dict(d)
        return None

    def find_range(self, partition: Any, lo_exclusive: Any,
                   hi_inclusive: Any = None,
                   stats: Optional[dict] = None) -> List[dict]:
        """Copies of the partition's rows with lo < order <= hi (no upper
        bound when hi is None), ordered by order. stats, if given,
        receives "scanned": the rows the read looked at."""
        if self._range_key is None:
            raise TypeError("find_range needs a collection with a range_key")
        with self._lock:
            orders, rows = self._ranges.get(partition, ((), ()))
            a = bisect.bisect_right(orders, lo_exclusive)
            b = len(orders) if hi_inclusive is None \
                else bisect.bisect_right(orders, hi_inclusive)
            out = [dict(r) for r in rows[a:b]]
        if stats is not None:
            stats["scanned"] = len(out)
        return out

    def upsert(self, match: Callable[[dict], bool], doc: dict) -> None:
        if self._range_key is not None:
            # A replaced row would leave its index entry stale.
            raise TypeError("upsert on a collection with a range_key")
        with self._lock:
            for i, d in enumerate(self._docs):
                if match(d):
                    self._docs[i] = dict(doc)
                    return
            self._docs.append(dict(doc))

    def __len__(self) -> int:
        with self._lock:
            return len(self._docs)


class DatabaseManager:
    """Named collections per (tenant, document) style keys."""

    def __init__(self):
        self._collections: Dict[str, Collection] = {}
        self._lock = threading.Lock()

    def collection(self, name: str,
                   unique_key: Optional[Callable[[dict], Any]] = None,
                   range_key: Optional[Callable[[dict], Tuple[Any, Any]]]
                   = None) -> Collection:
        with self._lock:
            if name not in self._collections:
                self._collections[name] = Collection(unique_key, range_key)
            return self._collections[name]
