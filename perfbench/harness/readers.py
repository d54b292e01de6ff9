"""Shared arithmetic of the per-layer readers (metrics/<name>.py). A
reader that finds nothing to read returns None and the harness leaves
the metric out of the line."""

import numpy as np

from .trace import _clip, _union


def idle_share(run):
    return run.trace["idle_share"] if run.trace else None


def percentile(samples, q: float):
    if samples is None or not len(samples):
        return None
    return float(np.percentile(np.asarray(samples), q))


def join_span_ms(run, name: str):
    """Per join of the window: the milliseconds that the program's
    ``name`` spans cover inside the harness's ``join`` span (their union,
    clipped to the join, so a span nested in one of its own name counts
    once). None where no such span lies inside any join. Traced runs put
    the program's spans beside the harness's, as ``fluid.<name>``."""
    spans = run.spans.spans
    found = spans.get("fluid." + name)
    if not found or run.t0 is None:
        return None
    per_join = [sum(b - a for a, b in _union(_clip(found, lo, hi))) * 1e3
                for lo, hi in spans.get("join", ()) if lo >= run.t0]
    return per_join if any(per_join) else None
