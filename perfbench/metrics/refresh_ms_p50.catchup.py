from perfbench.harness.readers import join_span_ms, percentile


def read(run):
    """Per join: ms under the program's ``catchup.refresh`` (the read
    tier's catch-up artifact refreshed); the median over joins."""
    return percentile(join_span_ms(run, "catchup.refresh"), 50)
