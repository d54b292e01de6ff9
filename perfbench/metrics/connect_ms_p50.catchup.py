from perfbench.harness.readers import join_span_ms, percentile


def read(run):
    """Per join: ms under the program's ``load.connect`` (the join op's
    pump, the delta reads, the residue replayed); the median over joins."""
    return percentile(join_span_ms(run, "load.connect"), 50)
