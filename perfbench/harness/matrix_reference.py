"""The plain reference of the matrix cell's semantics, independent of the
program: it imports nothing of ``fluidframework_tpu``.

``Matrix``: a SharedMatrix under its ops applied in seq order. Rows and
columns are lists of stable ids (``"<nonce>.<counter>.<offset>"``); an
axis insert splices the ids of its run in, an axis remove takes ids out.
Cells live in a dict keyed ``"<row id>|<col id>"``, so a cell of a
removed row or column stays, keyed by identity, and the visible grid is
the cells whose row and column are both visible. The traffic's writers
always see the head (matrix_traffic.py), so no op is concurrent with
another and plain sequential application is the semantics.
"""

import hashlib
import json
from typing import Iterable, List, Tuple


def run_ids(run: list) -> List[str]:
    """The ids of a wire run ``[nonce, counter, start, length]``."""
    nonce, counter, start, length = run
    return [f"{nonce}.{counter}.{start + k}" for k in range(length)]


def digest(rows: int, cols: int,
           triples: Iterable[Tuple[int, int, object]]) -> str:
    """A grid as its dimensions and its sorted non-empty (row, col,
    value) triples, hashed."""
    body = json.dumps([rows, cols, sorted(triples)], sort_keys=True)
    return hashlib.sha256(body.encode()).hexdigest()


def grid_triples(row_ids: List[str], col_ids: List[str], cells: dict):
    """The non-empty (row, col, value) triples of the visible grid."""
    row_at = {r: i for i, r in enumerate(row_ids)}
    col_at = {c: j for j, c in enumerate(col_ids)}
    out = []
    for key, value in cells.items():
        if value is None:
            continue
        r, _, c = key.partition("|")
        i, j = row_at.get(r), col_at.get(c)
        if i is not None and j is not None:
            out.append((i, j, value))
    return out


class Matrix:
    def __init__(self, row_ids: List[str], col_ids: List[str]):
        self.rows = list(row_ids)
        self.cols = list(col_ids)
        self.cells: dict = {}

    def apply(self, op: dict) -> None:
        target = op["target"]
        if target == "cell":
            self.cells[op["key"]] = op["value"]
            return
        axis = self.rows if target == "rows" else self.cols
        edit = op["op"]
        if edit["type"] == 0:
            p = edit["pos1"]
            axis[p:p] = run_ids(edit["seg"]["run"])
        elif edit["type"] == 1:
            del axis[edit["pos1"]:edit["pos2"]]

    def digest(self) -> str:
        return digest(len(self.rows), len(self.cols),
                      grid_triples(self.rows, self.cols, self.cells))

    def window(self, row: int, col: int, n_rows: int, n_cols: int):
        """The cells of the window at (row, col), clipped at the edges."""
        cols = self.cols[col:col + n_cols]
        return [[self.cells.get(r + "|" + c) for c in cols]
                for r in self.rows[row:row + n_rows]]
