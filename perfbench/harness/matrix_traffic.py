"""The load generator of the matrix cell: a seeded spreadsheet storm on
one SharedMatrix, as SharedMatrix's own wire ops.

The mix is testing/traces.py ``matrix_storm``'s (BASELINE config #3):
6% row inserts, 4% column inserts, 1% row removes and 1% column removes
(each only while that axis holds more than 8), and 88% cell sets that
move by -2..+2 on each axis around an active cell, 20% of them jumping
to a random cell. The draws are vectorized per batch; the dimensions are
tracked so every op is valid where it is emitted.

The ops are dds/matrix.py's submit shapes: ``insert_rows`` /
``insert_cols`` (a merge insert of a one-id run), ``remove_rows`` /
``remove_cols`` (a merge remove) and ``set_cell`` (a cell write keyed by
the stable row and column ids). Each writer allocates its run ids as
PermutationVector does: its own nonce, then a counter per insert. The
writers see the head when they send (traffic.Fleet's boxcars), so no op
is concurrent with another.
"""

import json
from typing import List

import numpy as np

from .matrix_reference import run_ids

GRID = "g"


class Storm:
    """The writers' view of the sheet (row and column ids in order, the
    active cell) and the seeded draws of the next ops."""

    def __init__(self, row_ids: List[str], col_ids: List[str], seed: int,
                 writers: int):
        self.rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
        self.rows = list(row_ids)
        self.cols = list(col_ids)
        self.active = [0, 0]
        self.nonces = [int(n) for n in self.rng.integers(1, 2**48, writers)]
        self.counters = [0] * writers
        self.emitted = 0

    def ops(self, n: int, writer: int) -> List[str]:
        """``n`` storm ops by ``writer`` as wire JSON; a set's value is
        the op's index in the whole storm."""
        rng = self.rng
        roll = rng.random(n)
        at = rng.random(n)
        local = rng.random(n) < 0.8
        step = rng.integers(-2, 3, (n, 2))
        jump = rng.random((n, 2))
        value = self.emitted
        out = []
        for i in range(n):
            r, c = len(self.rows), len(self.cols)
            if roll[i] < 0.06:
                out.append(self._insert("rows", int(at[i] * (r + 1)),
                                        writer))
            elif roll[i] < 0.10:
                out.append(self._insert("cols", int(at[i] * (c + 1)),
                                        writer))
            elif roll[i] < 0.11 and r > 8:
                out.append(self._remove("rows", int(at[i] * (r - 1))))
            elif roll[i] < 0.12 and c > 8:
                out.append(self._remove("cols", int(at[i] * (c - 1))))
            else:
                a = self.active
                if local[i]:
                    a[0] = min(max(a[0] + int(step[i, 0]), 0), r - 1)
                    a[1] = min(max(a[1] + int(step[i, 1]), 0), c - 1)
                else:
                    a[0], a[1] = int(jump[i, 0] * r), int(jump[i, 1] * c)
                key = self.rows[a[0]] + "|" + self.cols[a[1]]
                out.append(json.dumps({"target": "cell", "key": key,
                                       "value": value + i}))
        self.emitted = value + n
        return out

    def _insert(self, axis: str, at: int, writer: int) -> str:
        self.counters[writer] += 1
        run = [self.nonces[writer], self.counters[writer], 0, 1]
        getattr(self, axis)[at:at] = run_ids(run)
        self._clamp()
        return json.dumps({"target": axis, "op": {
            "type": 0, "pos1": at, "seg": {"run": run}}})

    def _remove(self, axis: str, at: int) -> str:
        del getattr(self, axis)[at]
        self._clamp()
        return json.dumps({"target": axis, "op": {
            "type": 1, "pos1": at, "pos2": at + 1}})

    def _clamp(self) -> None:
        self.active[0] = min(self.active[0], len(self.rows) - 1)
        self.active[1] = min(self.active[1], len(self.cols) - 1)
