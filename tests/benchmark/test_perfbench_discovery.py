"""The harness is driven by data: a configuration, a traffic mix, its
test-size cut and a per-layer metric reader added as new files, and
entries in BENCHMARK.json, run by name with no edit to an existing file;
the seeded traffic is the same for one seed and differs for another;
each per-layer metric is reported in a traced run of every cell it names
(a device-trace reader on a synthetic trace, since a CPU rehearsal has
no device planes) and reads nothing where there is nothing; without a
chip there is no result."""

import copy
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from perfbench_tiny import (REPO, TINY, apply_cuts, make_tiny_root,
                            run_cell, synthetic_trace)
from perfbench.harness import peaks, spec, trace, traffic
from perfbench.harness.clock import Spans


def _bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _add(root: str, rel: str, data) -> None:
    path = os.path.join(root, rel)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(data if isinstance(data, str) else json.dumps(data))


def metric_cells(root: str):
    """(per-layer metric, cell) for each cell where the metric applies:
    those its ``workloads`` lists, or every cell."""
    bench = _bench(root)
    cells = [w["name"] for w in bench["workloads"]]
    return [(m["name"], c) for m in bench["per_layer"]
            for c in m.get("workloads", cells)]


def _first_cells(root: str):
    """(per-layer metric, the first cell where it applies)."""
    first = {}
    for metric, cell in metric_cells(root):
        first.setdefault(metric, (metric, cell))
    return list(first.values())


def _id(pair) -> str:
    metric, cell = pair
    cells = [c for m, c in metric_cells(REPO) if m == metric]
    return metric if len(cells) == 1 else f"{metric}@{cell}"


def _reader(root: str, metric: str, cell: str):
    return spec.metric_readers(spec.load_cell(root, cell))[metric]


def _nothing_in(run):
    """The run as one with nothing to read: every list of samples empty,
    no spans, and its trace, counts and times None."""
    def empty(value):
        if isinstance(value, Spans):
            return Spans(annotate=False)
        return [] if isinstance(value, (list, tuple, np.ndarray)) else None
    return SimpleNamespace(**{k: empty(v) for k, v in vars(run).items()})


def check_reported(root: str, metric: str, cell: str, line: dict, run):
    """A metric whose number the rehearsal can produce is in its traced
    line; a device-trace metric reads a number from the run with a
    synthetic trace in place of the CPU's, which has no device planes."""
    entry = next(m for m in _bench(root)["per_layer"] if m["name"] == metric)
    if entry["source"] == "device_trace":
        traced = copy.copy(run)
        traced.trace = trace.reduce(synthetic_trace(
            ops=[("fusion", 10, 40)], modules=[("jit_step(1)", 10, 40)]))
        value = _reader(root, metric, cell)(traced)
    else:
        assert metric in line["metrics"], (metric, cell,
                                           sorted(line["metrics"]))
        assert line["metrics"][metric]["unit"] == entry["unit"]
        value = line["metrics"][metric]["value"]
    assert value is not None and value >= 0, (metric, cell, value)


def check_nothing_to_read(root: str, metric: str, cell: str, run):
    assert _reader(root, metric, cell)(_nothing_in(run)) is None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """cell -> (result line, run object) of one traced rehearsal of the
    cell, run once for the module."""
    root = make_tiny_root(tmp_path_factory.mktemp("traced"))
    runs = {}

    def get(cell: str):
        if cell not in runs:
            kept = {}
            line = run_cell(root, cell, trace=1, kept=kept)
            assert line["correct"] is True, line["checks"]
            runs[cell] = (line, kept["run"])
        return runs[cell]

    get.root = root
    return get


def test_new_cell_config_and_metric_by_name(tiny_root):
    with open(os.path.join(REPO, "perfbench", "configs",
                           "hotdoc_string_100k.json")) as f:
        config = json.load(f)
    config["name"] = "tiny_doc"
    _add(tiny_root, "perfbench/configs/tiny_doc.json", config)
    with open(os.path.join(REPO, "perfbench", "workloads",
                           "hotdoc100k.catchup.json")) as f:
        mix = json.load(f)
    mix["writers_per_doc"] = 3
    _add(tiny_root, "perfbench/workloads/tiny.catchup.json", mix)
    _add(tiny_root, os.path.join(TINY, "configs", "tiny_doc.json"),
         {"history_ops": 200, "summary_every_ops": 40})
    _add(tiny_root, os.path.join(TINY, "workloads", "tiny.catchup.json"),
         {"boxcar_ops": 10, "warmup_joins": 1, "tail_step_ops": 10,
          "rehearsal_joins": 0})
    _add(tiny_root, "perfbench/metrics/joins_per_s.tiny.py",
         "def read(run):\n"
         "    if not run.window_s:\n"
         "        return None\n"
         "    return len(run.catchup_ms) / run.window_s\n")
    _add(tiny_root, "perfbench/metrics/adopt_ms_p50.tiny.py",
         "from perfbench.harness.readers import join_span_ms, percentile\n"
         "\n\n"
         "def read(run):\n"
         "    return percentile(join_span_ms(run, 'load.adopt'), 50)\n")
    bench = _bench(tiny_root)
    bench["configs"].append({"name": "tiny_doc", "source": "test",
                             "file": "perfbench/configs/tiny_doc.json",
                             "reduced": ["history_ops"], "why": "test"})
    bench["workloads"].append({"name": "tiny.catchup", "config": "tiny_doc",
                               "traffic": "tiny.catchup", "chips": 1,
                               "why": "test"})
    bench["per_layer"] += [
        {"name": "joins_per_s.tiny", "unit": "1/s", "better": "higher",
         "source": "host_clock", "layer": "read path",
         "moves": "catchup_ms_p50", "workloads": ["tiny.catchup"]},
        {"name": "adopt_ms_p50.tiny", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "read path",
         "moves": "catchup_ms_p50", "workloads": ["tiny.catchup"]}]
    for m in bench["end_to_end"]:
        if m["name"] == "catchup_ms_p50":
            m["workloads"].append("tiny.catchup")
    _add(tiny_root, "BENCHMARK.json", bench)
    apply_cuts(tiny_root)
    assert spec.load_cell(tiny_root, "tiny.catchup").config[
        "history_ops"] == 200

    kept = {}
    line = run_cell(tiny_root, "tiny.catchup", trace=1, kept=kept)
    assert line["correct"] is True, line["checks"]
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    pairs = [(m, c) for m, c in metric_cells(tiny_root)
             if c == "tiny.catchup"]
    assert pairs == [("joins_per_s.tiny", "tiny.catchup"),
                     ("adopt_ms_p50.tiny", "tiny.catchup")]
    for metric, cell in pairs:
        check_reported(tiny_root, metric, cell, line, kept["run"])
        check_nothing_to_read(tiny_root, metric, cell, kept["run"])
    assert set(line["metrics"]) == {m for m, _ in pairs}
    line = run_cell(tiny_root, "tiny.catchup", trace=0)
    assert set(line["metrics"]) == {"catchup_ms_p50", "setup_s"}


def _render(seed: int, n: int = 400):
    fleet = traffic.Fleet({"documents": 3}, {"writers_per_doc": 2}, seed)
    out = [fleet.join(d, w) for d in range(3) for w in range(2)]
    for i in range(n):
        ops = fleet.text_ops(np.arange(3))
        out += [fleet.boxcar(d, i % 2, [(traffic.TEXT, ops[d])])
                for d in range(3)]
    return out


def test_seeded_traffic_repeats_and_differs():
    big = 2**31 + 12345
    a, b, c = _render(big), _render(big), _render(big + 1)
    assert a == b
    assert a != c


def test_every_seed_walks_the_same_tails():
    """The seeded phase is a multiple of the tail step below the summary
    interval: every seed's joins see the same tails, in another order."""
    from perfbench.harness.read_cell import tail_phase

    with open(os.path.join(REPO, "perfbench", "workloads",
                           "hotdoc100k.catchup.json")) as f:
        step = json.load(f)["tail_step_ops"]
    with open(os.path.join(REPO, "perfbench", "configs",
                           "hotdoc_string_100k.json")) as f:
        every = json.load(f)["summary_every_ops"]
    seeds = [1, 2**31 + 7, 2**33 + 5] + list(range(3_000_000_000,
                                                   3_000_000_040))
    phases = [tail_phase(s, step, every) for s in seeds]
    assert all(p % step == 0 and 0 <= p < every for p in phases)
    assert len(set(phases)) > 10
    assert tail_phase(seeds[1], step, every) == phases[1]


@pytest.mark.parametrize("pair", metric_cells(REPO), ids=_id)
def test_each_metric_has_its_reader(traced, pair):
    metric, cell = pair
    line, run = traced(cell)
    check_reported(traced.root, metric, cell, line, run)


@pytest.mark.parametrize("pair", _first_cells(REPO), ids=_id)
def test_reader_with_nothing_to_read_gives_none(traced, pair):
    metric, cell = pair
    check_nothing_to_read(traced.root, metric, cell, traced(cell)[1])


# The metrics that split a join by the program's spans, and each one's span.
JOIN_SPANS = {"refresh_ms_p50.catchup": "catchup.refresh",
              "extract_ms_p50.catchup": "catchup.extract",
              "connect_ms_p50.catchup": "load.connect"}


def _join_run(spans: dict):
    """A run of two joins in a window from 0 to 1 s (and one warm-up join
    before it) holding ``spans``: {name: [(start_s, end_s)]}."""
    held = Spans(annotate=False)
    held.spans["join"] += [(-0.5, -0.4), (0.1, 0.2), (0.3, 0.5)]
    for name, intervals in spans.items():
        held.spans[name] += intervals
    return SimpleNamespace(spans=held, t0=0.0, t1=1.0)


@pytest.mark.parametrize("metric", sorted(JOIN_SPANS))
def test_join_span_reader_takes_the_median_inside_joins(tiny_root, metric):
    name = "fluid." + JOIN_SPANS[metric]
    run = _join_run({
        "fluid.load.resolve": [(0.105, 0.195), (0.305, 0.495)],
        "fluid.load.fetch": [(0.32, 0.37)],
        # Join 1: 30 ms, a span of its own name nested inside counted once.
        # Join 2: 50 ms at depth 2 (under load.fetch) and 10 ms at depth 1.
        # Outside every join of the window: a warm-up join's, one between
        # joins, one after the last.
        name: [(0.11, 0.14), (0.12, 0.13), (0.32, 0.37), (0.40, 0.41),
               (-0.45, -0.42), (0.25, 0.28), (0.6, 0.9)]})
    assert _reader(tiny_root, metric, "hotdoc100k.catchup")(run) == \
        pytest.approx(45.0)


@pytest.mark.parametrize("metric", sorted(JOIN_SPANS))
def test_join_span_reader_without_its_spans_gives_none(tiny_root, metric):
    read = _reader(tiny_root, metric, "hotdoc100k.catchup")
    name = "fluid." + JOIN_SPANS[metric]
    assert read(_join_run({"fluid.load.resolve": [(0.105, 0.195)]})) is None
    assert read(_join_run({name: [(0.25, 0.28), (0.6, 0.9)]})) is None


def test_unknown_names_are_errors(tiny_root):
    with pytest.raises(KeyError):
        spec.load_cell(tiny_root, "no.such.cell")
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_chip_no_result(tiny_root):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "hotdoc100k.catchup", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tiny_root, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
