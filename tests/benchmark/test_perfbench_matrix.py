"""The matrix cell at test size: an unbroken rehearsal of
``matrix1k.catchup`` is correct and every join adopts the read tier's
matrix artifact; the control and each planted fault of its timed path
(perfbench/harness/matrix_faults.py) turn `correct` false; the cell's
per-layer readers take the median over joins of the program's spans and
read nothing where there is nothing; the LWW apply's roofline share
reads the window's byte counter over the apply program's device time."""

from types import SimpleNamespace

import pytest

from perfbench_tiny import run_cell, synthetic_trace
from perfbench.harness import matrix_faults, matrix_reference, roofline, \
    spec, trace
from perfbench.harness.clock import Spans

CELL = "matrix1k.catchup"


def test_unbroken_run_is_correct_and_adopts(tiny_root):
    line = run_cell(tiny_root, CELL)
    assert line["correct"] is True, line["checks"]
    checks = line["checks"]
    assert checks["joins_checked"]["value"] >= 2
    assert checks["joins_adopted"]["value"] == \
        checks["joins_checked"]["value"]
    assert checks["summaries_checked"]["value"] >= 1
    assert {"catchup_ms_p50", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("fault", sorted(matrix_faults.INSTALL))
def test_broken_run_is_not_correct(tiny_root, fault):
    undo = matrix_faults.INSTALL[fault]()
    try:
        line = run_cell(tiny_root, CELL)
    finally:
        undo()
    assert line["correct"] is False, (fault, line["checks"])


# The cell's metrics that split a join by the program's spans.
JOIN_SPANS = {"matrix_refresh_ms_p50.matrix": "catchup.matrix",
              "matrix_adopt_ms_p50.matrix": "matrix.adopt"}


def _join_run(spans: dict):
    """Two joins in a window from 0 to 1 s, one warm-up join before it."""
    held = Spans(annotate=False)
    held.spans["join"] += [(-0.5, -0.4), (0.1, 0.2), (0.3, 0.5)]
    for name, intervals in spans.items():
        held.spans[name] += intervals
    return SimpleNamespace(spans=held, t0=0.0, t1=1.0)


def _reader(root: str, metric: str):
    return spec.metric_readers(spec.load_cell(root, CELL))[metric]


@pytest.mark.parametrize("metric", sorted(JOIN_SPANS))
def test_join_span_reader_takes_the_median_inside_joins(tiny_root, metric):
    name = "fluid." + JOIN_SPANS[metric]
    run = _join_run({name: [(0.11, 0.13), (0.32, 0.36), (0.40, 0.42),
                            (-0.45, -0.42), (0.25, 0.28), (0.6, 0.9)]})
    # Join 1: 20 ms; join 2: 60 ms; the rest lie outside every join.
    assert _reader(tiny_root, metric)(run) == pytest.approx(40.0)


@pytest.mark.parametrize("metric", sorted(JOIN_SPANS))
def test_join_span_reader_without_its_spans_gives_none(tiny_root, metric):
    read = _reader(tiny_root, metric)
    name = "fluid." + JOIN_SPANS[metric]
    assert read(_join_run({})) is None
    assert read(_join_run({name: [(0.25, 0.28), (0.6, 0.9)]})) is None


def _lww_run(modules, bytes_moved):
    reduced = trace.reduce(synthetic_trace(
        ops=[(n, a, b) for n, a, b in modules], modules=modules))
    counters0 = {"lww.apply.bytes": 1000.0}
    counters1 = {"lww.apply.bytes": 1000.0 + bytes_moved}
    return SimpleNamespace(trace=reduced, counters0=counters0,
                           counters1=counters1)


def test_lww_apply_share_reads_bytes_over_device_time():
    run = _lww_run([("jit_apply_lww_batched_1_", 10, 30),
                    ("jit_apply_lww_batched_2_", 40, 50),
                    ("jit_other(1)", 60, 90)], bytes_moved=8e6)
    # 8 MB in 30 ms of the apply programs at 1 GB/s: 8e6 / 3e7.
    assert roofline.lww_apply_share(run, bytes_per_s=1e9) == \
        pytest.approx(8e6 / 3e7)


@pytest.mark.parametrize("case", ["no_program", "no_counter", "no_trace"])
def test_lww_apply_share_with_nothing_to_read_gives_none(case):
    run = _lww_run([("jit_other(1)", 10, 40)] if case == "no_program"
                   else [("jit_apply_lww_batched_1_", 10, 40)], 8e6)
    if case == "no_counter":
        run.counters0, run.counters1 = {}, {"other": 1.0}
    if case == "no_trace":
        run.trace = None
    assert roofline.lww_apply_share(run, bytes_per_s=1e9) is None


def test_reference_keeps_removed_rows_cells_by_identity():
    ref = matrix_reference.Matrix(["r0", "r1"], ["c0"])
    ref.apply({"target": "cell", "key": "r1|c0", "value": 7})
    ref.apply({"target": "rows", "op": {"type": 1, "pos1": 1, "pos2": 2}})
    assert ref.window(0, 0, 5, 5) == [[None]]
    assert ref.cells == {"r1|c0": 7}
    ref.apply({"target": "rows", "op": {"type": 0, "pos1": 0,
                                        "seg": {"run": [9, 1, 0, 2]}}})
    assert ref.rows == ["9.1.0", "9.1.1", "r0"]
    assert ref.digest() == matrix_reference.digest(3, 1, [])
