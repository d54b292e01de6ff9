"""Op-tensor gRPC bridge: packed partition batches through the device
pipeline (BASELINE north star: the Node↔device hop amortized via
partition-sized batches)."""

import jax.numpy as jnp
import numpy as np
import pytest

grpc = pytest.importorskip("grpc")

from fluidframework_tpu.testing.traces import gen_traces  # noqa: E402
from fluidframework_tpu.mergetree.oppack import PackedOps  # noqa: E402
from fluidframework_tpu.mergetree.state import make_state  # noqa: E402
from fluidframework_tpu.server import ticket_kernel as tk  # noqa: E402
from fluidframework_tpu.server.bridge import (OpBridgeClient,  # noqa: E402
                                              OpBridgeServer, decode_ops,
                                              encode_ops)
from fluidframework_tpu.server.pipeline import full_step  # noqa: E402

DOCS, STEPS = 8, 20


def direct_result(cols):
    ops = PackedOps(**{f: jnp.asarray(cols[f]) for f in PackedOps._fields})
    raw = tk.RawOps(client=ops.client, client_seq=ops.seq,
                    ref_seq=ops.ref_seq)
    tstate = tk.make_ticket_state(8, batch=DOCS)
    mstate = make_state(64, 1, batch=DOCS)
    tstate, mstate, ticketed, total = full_step(tstate, mstate, raw, ops)
    return np.asarray(ticketed.seq), np.asarray(total)


class TestFraming:
    def test_roundtrip(self):
        cols = gen_traces(DOCS, STEPS, seed=2)
        b, t, decoded = decode_ops(encode_ops(cols))
        assert (b, t) == (DOCS, STEPS)
        for field in PackedOps._fields:
            np.testing.assert_array_equal(decoded[field],
                                          np.asarray(cols[field], np.int32))


class TestBridge:
    def test_batch_matches_direct_pipeline(self):
        server = OpBridgeServer(capacity=64).start()
        try:
            client = OpBridgeClient(server.address)
            assert client.ping()
            cols = gen_traces(DOCS, STEPS, seed=2)
            reply = client.submit_batch(cols)
            seq_direct, total_direct = direct_result(cols)
            np.testing.assert_array_equal(reply["seq"], seq_direct)
            np.testing.assert_array_equal(reply["totalLen"], total_direct)
            client.close()
        finally:
            server.stop()

    def test_session_state_persists_across_batches(self):
        server = OpBridgeServer(capacity=128).start()
        try:
            client = OpBridgeClient(server.address, session_id="s1")
            first = gen_traces(DOCS, STEPS, seed=3)
            r1 = client.submit_batch(first)
            # Continuation batch: clientSeq/refSeq advance past batch one.
            cont = gen_traces(DOCS, STEPS, seed=4)
            for field in ("seq",):
                cont[field] = cont[field] + STEPS
            cont["ref_seq"] = cont["ref_seq"] + STEPS
            r2 = client.submit_batch(cont)
            # Sequence numbers continue monotonically per document.
            assert (r2["seq"].max(axis=1) > r1["seq"].max(axis=1)).all()
            # Documents kept their content: lengths only grow or shrink from
            # the continued state, never reset to batch-one totals.
            assert (r2["totalLen"] != 0).any()
            client.close()
        finally:
            server.stop()

    def test_isolated_sessions(self):
        server = OpBridgeServer(capacity=64).start()
        try:
            a = OpBridgeClient(server.address, session_id="a")
            b = OpBridgeClient(server.address, session_id="b")
            cols = gen_traces(DOCS, STEPS, seed=5)
            ra = a.submit_batch(cols)
            rb = b.submit_batch(cols)  # same ops, fresh session: same result
            np.testing.assert_array_equal(ra["seq"], rb["seq"])
            np.testing.assert_array_equal(ra["totalLen"], rb["totalLen"])
            a.close()
            b.close()
        finally:
            server.stop()
