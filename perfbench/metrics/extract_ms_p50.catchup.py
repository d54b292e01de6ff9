from perfbench.harness.readers import join_span_ms, percentile


def read(run):
    """Per join: ms under the program's ``catchup.extract`` (the device
    extraction, its readback and assembly); the median over joins."""
    return percentile(join_span_ms(run, "catchup.extract"), 50)
