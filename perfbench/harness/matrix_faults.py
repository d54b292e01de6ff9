"""The matrix cell's control and planted faults, to show that the
comparison deciding ``correct`` fails when the timed path is broken
(faults.py does the same for the read cell). The benchmark's own runs
never install one; tests/benchmark/test_perfbench_matrix.py and the chip
runs that check the cell (PERF.md) do.

Each entry patches the program in this process only and returns a
function that undoes the patch.
"""

from typing import Callable, Dict

from .faults import _patch, _stale_catchup


def _dropped_cell_set():
    """Fault: the server drops a cell write — every seventh sequenced
    write to a matrix's cell lane never reaches the device."""
    from fluidframework_tpu.server import tpu_sequencer as ts

    real = ts.TpuSequencerLambda._route_lww

    def route(self, streams, key, op, seq, seeded_before):
        if key[2].endswith(ts.MATRIX_CELLS_SUFFIX) and seq % 7 == 0:
            return
        real(self, streams, key, op, seq, seeded_before)

    return _patch(ts.TpuSequencerLambda, "_route_lww", route)


def _viewport_altered():
    """Fault: the late client's sheet altered where it is read — its row
    order reads reversed."""
    from fluidframework_tpu.dds.matrix import PermutationVector

    real = PermutationVector.ids_in_order

    def ids_in_order(self):
        return real(self)[::-1]

    return _patch(PermutationVector, "ids_in_order", ids_in_order)


def _dims_altered():
    """Fault: the late client's dimensions altered where they are read —
    its row count reads one short."""
    from fluidframework_tpu.dds.matrix import SharedMatrix

    real = SharedMatrix.row_count
    return _patch(SharedMatrix, "row_count",
                  property(lambda self: real.fget(self) - 1))


def _grid_altered():
    """Fault: the late client's grid altered where it is produced — a
    matrix's state loads without one of its visible cells."""
    from fluidframework_tpu.dds.matrix import SharedMatrix

    real = SharedMatrix._load_parts

    def load_parts(self, parts, client_id):
        real(self, parts, client_id)
        rows = {"%d.%d.%d" % i for i in self.rows.ids_in_order()}
        cols = {"%d.%d.%d" % i for i in self.cols.ids_in_order()}
        for key, value in self.cells.items():
            row, _, col = key.partition("|")
            if value is not None and row in rows and col in cols:
                del self.cells[key]
                break

    return _patch(SharedMatrix, "_load_parts", load_parts)


def _creator_ids_altered():
    """Fault: the program allocates run ids off by one — every axis
    insert, the creator's too, takes the counter after the right one."""
    from fluidframework_tpu.dds.matrix import PermutationVector

    real = PermutationVector.insert_local

    def insert_local(self, pos, count):
        self.run_counter += 1
        return real(self, pos, count)

    return _patch(PermutationVector, "insert_local", insert_local)


INSTALL: Dict[str, Callable[[], Callable[[], None]]] = {
    "matrix.control": _stale_catchup,
    "matrix.dropped_cell_set": _dropped_cell_set,
    "matrix.viewport_altered": _viewport_altered,
    "matrix.dims_altered": _dims_altered,
    "matrix.grid_altered": _grid_altered,
    "matrix.creator_ids_altered": _creator_ids_altered,
}
