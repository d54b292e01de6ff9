from perfbench.harness.readers import join_span_ms, percentile


def read(run):
    """Per join: ms under the program's ``catchup.matrix`` (the read
    tier's matrix channel: the cell lane's read, the axes' packing and
    the compose); the median over joins."""
    return percentile(join_span_ms(run, "catchup.matrix"), 50)
