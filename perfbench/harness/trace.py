"""From a profiler trace to numbers: busy union, idle share, device time
per program, and idle gaps named by what the host was doing.

``capture`` takes a JAX profiler trace (Python tracing off; host
TraceAnnotations on) into a temporary directory and returns it as plain
data; ``reduce`` works on that plain data alone, so tests feed it a
small synthetic trace."""

import glob
import os
import shutil
import tempfile
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]   # name, start_ns, duration_ns

WINDOW = "bench.window"


def to_plain(profile) -> List[dict]:
    """jax.profiler.ProfileData -> [{"name", "lines": [{"name",
    "events": [(name, start_ns, duration_ns)]}]}]."""
    return [{"name": plane.name,
             "lines": [{"name": line.name,
                        "events": [(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in line.events]}
                       for line in plane.lines]}
            for plane in profile.planes]


@contextmanager
def capture(out: dict):
    """Trace the body; afterwards ``out["planes"]`` holds the plain trace.
    The trace directory is deleted again."""
    import jax

    path = tempfile.mkdtemp(prefix="perfbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                              recursive=True)
            if files:
                out["planes"] = to_plain(
                    jax.profiler.ProfileData.from_file(files[0]))
        finally:
            shutil.rmtree(path, ignore_errors=True)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def device_planes(planes: List[dict]) -> List[dict]:
    return [p for p in planes if p["name"].startswith("/device:")
            and any(line["events"] for line in p["lines"])]


def _line(plane: dict, *names: str) -> List[Event]:
    for name in names:
        for line in plane["lines"]:
            if line["name"] == name and line["events"]:
                return line["events"]
    return []


def annotations(planes: List[dict]) -> List[Event]:
    """Host TraceAnnotations of the harness (``bench.*``) and of the
    program's traced stages (``fluid.*``), from every host thread."""
    return [e for p in planes if p["name"].startswith("/host:")
            for line in p["lines"] for e in line["events"]
            if e[0].startswith(("bench.", "fluid."))]


def reduce(planes: List[dict], programs: Dict[str, str] = None
           ) -> Optional[dict]:
    """The traced window's numbers, or None when the trace holds no
    window annotation or no device activity.

    - ``window_s``: the ``bench.window`` annotation's length;
    - ``busy_s``: union of device op intervals inside the window,
      averaged over the device planes that ran anything;
    - ``idle_share``: 1 - busy_s / window_s;
    - ``program_s``: {key: seconds} — device time of the module events
      whose name contains ``programs[key]``, summed over devices;
    - ``device_ops``: the 10 programs (module events; op events where a
      plane has no module line) with the most device time;
    - ``idle_gaps``: the 10 longest gaps between busy intervals, each
      named by the innermost ``bench.*`` or ``fluid.*`` annotation over
      its middle."""
    notes = annotations(planes)
    window = [e for e in notes if e[0] == WINDOW]
    devices = device_planes(planes)
    if not window or not devices:
        return None
    lo = window[0][1]
    hi = lo + window[0][2]
    busy_total = 0.0
    op_time: Dict[str, float] = {}
    program_s = {k: 0.0 for k in (programs or {})}
    gaps: List[Tuple[float, float]] = []
    for plane in devices:
        ops = _line(plane, "XLA Ops", "Ops") or _line(plane, "XLA Modules")
        busy = _clip(_union([(s, s + d) for _, s, d in ops]), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        modules = _line(plane, "XLA Modules")
        for name, s, d in (modules or ops):
            if s < hi and s + d > lo:
                short = name.split(" = ")[0][:120]
                op_time[short] = op_time.get(short, 0.0) + d
        for name, s, d in modules:
            if s < hi and s + d > lo:
                for key, needle in (programs or {}).items():
                    if needle in name:
                        program_s[key] += min(s + d, hi) - max(s, lo)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    window_s = (hi - lo) / 1e9
    busy_s = busy_total / len(devices) / 1e9

    def host_doing(t: float) -> str:
        over = [e for e in notes if e[0] != WINDOW and e[1] <= t < e[1] + e[2]]
        return min(over, key=lambda e: e[2])[0] if over else "none"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "program_s": {k: v / 1e9 for k, v in program_s.items()},
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[host_doing((a + b) / 2), (b - a) / 1e9]
                      for a, b in longest],
    }
