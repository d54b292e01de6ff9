"""The reduction from a profiler trace to the per-layer numbers, on a
small synthetic trace."""

from perfbench.harness import trace
from perfbench_tiny import synthetic_trace as _trace

MS = 1e6  # ns


def test_busy_union_idle_share_and_programs():
    planes = _trace(
        ops=[("fusion.1", 10, 30), ("fusion.2", 20, 40), ("copy", 60, 70),
             ("late", 95, 130)],
        modules=[("jit_serve_megakernel(3)", 10, 40),
                 ("jit_other(1)", 60, 70)],
        notes=[("bench.pump_round", 0, 100), ("bench.flush", 40, 60)])
    out = trace.reduce(planes, {"megakernel": "serve_megakernel"})
    # Busy: [10,40] + [60,70] + [95,100] (clipped at the window's end).
    assert abs(out["busy_s"] - 0.045) < 1e-12
    assert abs(out["window_s"] - 0.1) < 1e-12
    assert abs(out["idle_share"] - 0.55) < 1e-12
    assert abs(out["program_s"]["megakernel"] - 0.030) < 1e-12
    assert out["device_ops"][0] == ["jit_serve_megakernel(3)", 30 * MS / 1e9]
    # Longest gaps: [70,95] under pump_round, [40,60] under flush.
    assert out["idle_gaps"][0] == ["bench.pump_round", 0.025]
    assert out["idle_gaps"][1] == ["bench.flush", 0.02]


def test_gaps_are_named_by_the_innermost_program_stage():
    planes = _trace(ops=[("a", 0, 10), ("b", 50, 100)],
                    notes=[("bench.join", 5, 60),
                           ("fluid.load.connect", 20, 45),
                           ("fluid.server.get_deltas", 30, 40),
                           ("other.annotation", 25, 35)])
    out = trace.reduce(planes)
    assert out["idle_gaps"] == [["fluid.server.get_deltas", 0.04]]


def test_busy_is_averaged_over_devices():
    planes = _trace(ops=[("a", 0, 50)])
    second = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [("a", 0.0, 10 * MS)]}]}
    out = trace.reduce(planes + [second])
    assert abs(out["busy_s"] - 0.03) < 1e-12


def test_nothing_to_read_gives_none():
    assert trace.reduce(_trace(ops=[])) is None
    planes = _trace(ops=[("a", 0, 5)])
    planes[0]["lines"][0]["events"] = []
    assert trace.reduce(planes) is None
