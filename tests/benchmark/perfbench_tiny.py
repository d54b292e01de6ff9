"""Helpers of the benchmark's CPU tests: a copy of the benchmark's files
in a temporary checkout, cut to a size a test run can hold, and one
rehearsal run in this process.

The cuts are data: ``tiny/configs/<config>.json`` and
``tiny/workloads/<traffic>.json`` hold the keys that change in that
configuration or traffic mix of the copy, so a later cell brings its cut
as a new file."""

import io
import json
import os
import shutil
from contextlib import contextmanager, redirect_stderr, redirect_stdout

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join("tests", "benchmark", "tiny")
MS = 1e6  # ns


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _rewrite(path: str, changes: dict) -> None:
    data = _load(path)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def apply_cuts(root: str) -> None:
    """Cut every configuration and traffic mix that ``root``'s
    BENCHMARK.json names by its file under ``root``'s ``TINY``. A missing
    cut is an error: no CPU test runs a cell at its full size."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cuts = [(os.path.join(root, TINY, "configs", c["name"] + ".json"),
             os.path.join(root, c["file"])) for c in bench["configs"]]
    cuts += [(os.path.join(root, TINY, "workloads", t + ".json"),
              os.path.join(root, "perfbench", "workloads", t + ".json"))
             for t in sorted({w["traffic"] for w in bench["workloads"]})]
    for cut, path in cuts:
        _rewrite(path, _load(cut))


def make_tiny_root(tmp_path) -> str:
    """A checkout holding BENCHMARK.json and the directories it names
    under ``paths``, every config and traffic mix cut to test size."""
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for path in _load(os.path.join(REPO, "BENCHMARK.json"))["paths"]:
        shutil.copytree(os.path.join(REPO, path), os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    apply_cuts(root)
    return root


@contextmanager
def _keeping_run(kept: dict):
    """Keep the run object that the harness hands its per-layer readers,
    as ``kept["run"]``."""
    from perfbench.harness import spec

    real = spec.metric_readers

    def readers(cell):
        def keep(read):
            def read_kept(run):
                kept["run"] = run
                return read(run)
            return read_kept
        return {name: keep(read) for name, read in real(cell).items()}

    spec.metric_readers = readers
    try:
        yield
    finally:
        spec.metric_readers = real


def run_cell(root: str, workload: str, seed: int = 3_000_000_019,
             seconds: float = 1.5, trace: int = 0, must_report: bool = True,
             kept: dict = None):
    """One rehearsal run in this process; the result line, parsed (None
    for a run that ended without one, where ``must_report`` is off). With
    ``kept``, a traced run's run object lands in ``kept["run"]``."""
    from perfbench import run

    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err), \
            _keeping_run({} if kept is None else kept):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace),
                       "--rehearse"], root=root)
    if not must_report and rc != 0:
        return None
    assert rc == 0, err.getvalue()[-3000:]
    return json.loads(out.getvalue().strip().splitlines()[-1])


def synthetic_trace(window=(0, 100), ops=(), modules=(), notes=()):
    """A plain profiler trace (trace.to_plain's form): one host line with
    the window and the host annotations ``notes``, one device with its
    ``ops`` and ``modules``; each event (name, start ms, end ms)."""
    def events(spec):
        return [(n, a * MS, (b - a) * MS) for n, a, b in spec]

    host = events([("bench.window", *window)] + list(notes))
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events(ops)},
            {"name": "XLA Modules", "events": events(modules)}]},
    ]
