from perfbench.harness.readers import join_span_ms, percentile


def read(run):
    """Per join: ms under the program's ``matrix.adopt`` (the late
    client's SharedMatrix adopting the artifact through load_core); the
    median over joins."""
    return percentile(join_span_ms(run, "matrix.adopt"), 50)
