#!/usr/bin/env python3
"""Bring-up smoke: the served sequencing + merge-tree path on the chip.

    python chip_smoke.py            # one chip: phases device..loader
    python chip_smoke.py --chips 4  # four chips: the dp-sharded paths only

The deployment is BASELINE.json config #5, the Routerlicious load test:
10,000 concurrent documents, each with a SharedString channel, one in
eight also carrying a SharedMatrix and a SharedDirectory, served on paged
lane memory through the serving megakernel. Ops come seeded from
testing/traces.py (keystroke_trace, matrix_storm, directory_merge_script).

One process owns the chip for its whole life; it starts no child that
touches JAX. It exits 1 at the first phase that fails, and where JAX finds
no TPU. Each earlier line is one JSON record of a phase: seconds, compile
seconds, counters, and smoke rates — host-clock figures of a smoke run,
not benchmark metrics. The last line is exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""

import argparse
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

SEED = 21
DOCS = 10_000
RICH_EVERY = 8          # every 8th doc also has a matrix + a directory
WAVES = 3               # flush waves served without draining between
TEXT_OPS = 8            # per doc per wave
RICH_OPS = 4            # matrix commands and directory commands per wave
MATRIX_SHAPE = (16, 8)  # the sheet each rich doc starts with
SAMPLE = 256            # seeded sample of docs checked against the oracle
STORE = "s"
TEXT, GRID, DIR = "t", "grid", "dir"


class SmokeFailure(Exception):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def compile_mark():
    """Where the compile ledger's counters stand (the program's one JAX
    compile listener: backend compiles, persistent-cache loads included,
    and cache hits/misses)."""
    from fluidframework_tpu.telemetry import counters

    return (time.perf_counter(), counters.get("compile.backend_ms"),
            counters.get("compile.cache_hits"),
            counters.get("compile.cache_misses"))


def compile_since(mark) -> dict:
    now = compile_mark()
    return {"seconds": now[0] - mark[0],
            "compile_s": (now[1] - mark[1]) / 1e3,
            "cache_hits": int(now[2] - mark[2]),
            "cache_misses": int(now[3] - mark[3])}


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def device_phase(chips: int) -> dict:
    import jax

    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX reports platform {devices[0].platform!r}")
    check(len(devices) >= chips,
          f"{chips} chips asked for, JAX reports {len(devices)}")
    from fluidframework_tpu.core.platform import enable_compile_cache

    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "compile_cache_dir": enable_compile_cache()}


# ---------------------------------------------------------------------------
# phase 2: kernel headline (ticket + fused merge apply + summary length)
# ---------------------------------------------------------------------------

def _op_text(doc: int, op: int, n: int) -> str:
    return "".join(chr(97 + (doc + 7 * op + i) % 26) for i in range(n))


def kernel_inputs(docs: int, n_ops: int, seed: int):
    """(host op columns, RawOps, PackedOps) of seeded editing traces."""
    import jax.numpy as jnp

    from fluidframework_tpu.mergetree.oppack import PackedOps
    from fluidframework_tpu.server import ticket_kernel as tk
    from fluidframework_tpu.testing.traces import gen_traces

    cols = gen_traces(docs, n_ops, seed=seed)
    ops = PackedOps(**{f: jnp.asarray(cols[f]) for f in PackedOps._fields})
    raw = tk.RawOps(client=ops.client, client_seq=ops.seq,
                    ref_seq=ops.ref_seq)
    return cols, raw, ops


def fresh_states(docs: int, capacity: int):
    from fluidframework_tpu.mergetree.state import make_state
    from fluidframework_tpu.server import ticket_kernel as tk

    return (tk.make_ticket_state(8, batch=docs),
            make_state(capacity, 1, batch=docs))


def oracle_text(cols, doc: int, admitted) -> str:
    """MergeTreeOracle replay of one doc's admitted ops (one client, so
    every op sees all of that client's earlier ops)."""
    from fluidframework_tpu.mergetree import MergeTreeOracle

    tree = MergeTreeOracle(local_client=-2)
    seq = 0
    for j in range(cols["kind"].shape[1]):
        if not admitted[j]:
            continue
        seq += 1
        if int(cols["kind"][doc, j]) == 1:
            n = int(cols["new_len"][doc, j])
            tree.insert_text(int(cols["pos1"][doc, j]), _op_text(doc, j, n),
                             seq - 1, 1, seq)
        else:
            tree.remove_range(int(cols["pos1"][doc, j]),
                              int(cols["pos2"][doc, j]), seq - 1, 1, seq)
        tree.update_seq(seq)
    return tree.get_text()


def kernel_phase(docs: int = DOCS, n_ops: int = 100, capacity: int = 256,
                 sample: int = 64, seed: int = SEED) -> dict:
    """server.pipeline.full_step with the fused Pallas apply, checked
    against MergeTreeOracle replays of a seeded sample of documents."""
    import jax
    import numpy as np

    from fluidframework_tpu.mergetree.constants import SEG_TEXT
    from fluidframework_tpu.mergetree.host import PayloadTable, extract_text
    from fluidframework_tpu.server.pipeline import make_full_step

    cols, raw, ops = kernel_inputs(docs, n_ops, seed)
    step = jax.jit(make_full_step(fused_apply=True), donate_argnums=(0, 1))
    out = step(*fresh_states(docs, capacity), raw, ops)
    lengths = np.asarray(out[3])
    # Second step on fresh state: compiled, so the host clock sees the run.
    tstate, mstate = fresh_states(docs, capacity)
    jax.block_until_ready((tstate, mstate))
    t0 = time.perf_counter()
    out = step(tstate, mstate, raw, ops)
    lengths2 = np.asarray(out[3])
    warm_s = time.perf_counter() - t0
    check((lengths == lengths2).all(), "full_step is not deterministic")
    check(not bool(np.asarray(out[1].overflow).any()),
          "kernel state overflowed")
    admitted = np.asarray(out[2].seq) > 0
    check(admitted.all(), f"{int((~admitted).sum())} ops not admitted")

    rng = random.Random(seed)
    picked = sorted(rng.sample(range(docs), min(sample, docs)))
    host = jax.device_get(jax.tree_util.tree_map(
        lambda x: x[np.asarray(picked)], out[1]))
    for i, d in enumerate(picked):
        payloads = PayloadTable()
        for j in range(n_ops):
            n = int(cols["new_len"][d, j])
            payloads.add_insert(SEG_TEXT, _op_text(d, j, n))
        want = oracle_text(cols, d, admitted[d])
        got = extract_text(host, payloads, doc=i)
        check(got == want, f"doc {d}: kernel text != oracle text")
        check(int(lengths[d]) == len(want),
              f"doc {d}: summary length {int(lengths[d])} != {len(want)}")
    return {"docs": docs, "ops_per_doc": n_ops, "capacity": capacity,
            "docs_checked": len(picked), "warm_step_s": warm_s,
            "smoke_ops_per_s": docs * n_ops / warm_s}


# ---------------------------------------------------------------------------
# phase 3: the served path
# ---------------------------------------------------------------------------

class _Outbox:
    """Stands in for a datastore runtime: a DDS bound to it hands its
    wire ops here instead of to a container."""

    id = STORE
    client_ordinal = 1

    def __init__(self):
        self.ops = []

    def submit_channel_op(self, channel_id, contents):
        self.ops.append((channel_id, contents))


def _subdir(directory, path):
    node = directory.root
    for name in path:
        node = node.get_sub_directory(name) or \
            node.create_sub_directory(name)
    return node


def _rich_ops(doc: int, seed: int):
    """Per-wave wire ops of one doc's SharedMatrix and SharedDirectory,
    produced by the DDS objects themselves from the seeded traces."""
    from fluidframework_tpu.dds.directory import SharedDirectory
    from fluidframework_tpu.dds.matrix import SharedMatrix
    from fluidframework_tpu.testing.traces import (directory_merge_script,
                                                   matrix_storm)

    random.seed(seed * 7919 + doc)  # matrix run-id nonces
    box = _Outbox()
    grid = SharedMatrix(GRID, runtime=box)
    tree = SharedDirectory(DIR, runtime=box)
    grid.connect()
    tree.connect()
    rows, cols = MATRIX_SHAPE
    storm = matrix_storm(rows, cols, WAVES * RICH_OPS, seed=seed + doc)
    script = directory_merge_script(WAVES * RICH_OPS, n_clients=1,
                                    seed=seed + doc)
    waves = []
    for w in range(WAVES):
        if w == 0:
            grid.insert_rows(0, rows)
            grid.insert_cols(0, cols)
        for cmd in storm[w * RICH_OPS:(w + 1) * RICH_OPS]:
            if cmd[0] == "set":
                grid.set_cell(cmd[1], cmd[2], cmd[3])
            else:
                getattr(grid, cmd[0])(cmd[1], cmd[2])
        for _c, path, cmd, *args in script[w * RICH_OPS:(w + 1) * RICH_OPS]:
            node = _subdir(tree, path)
            if cmd == "set":
                node.set(args[0], args[1])
            elif cmd == "delete":
                node.delete(args[0])
            elif cmd == "set_subdir_key":
                _subdir(tree, path + (args[0],)).set(args[1], args[2])
            else:
                node.clear()
        waves.append(box.ops)
        box.ops = []
    return waves


def served_traffic(docs: int = DOCS, seed: int = SEED):
    """[wave][QueuedMessage]: one raw wire boxcar per doc per wave."""
    from fluidframework_tpu.protocol.messages import (Boxcar,
                                                      DocumentMessage,
                                                      MessageType)
    from fluidframework_tpu.server.log import QueuedMessage
    from fluidframework_tpu.server.wire import boxcar_to_wire
    from fluidframework_tpu.testing.traces import keystroke_trace

    waves = [[] for _ in range(WAVES)]
    offset = 0
    for d in range(docs):
        doc, client = f"doc{d}", f"c{d}"
        text = [t[0] for t in keystroke_trace(WAVES * TEXT_OPS,
                                              seed=seed * 100_003 + d)]
        rich = _rich_ops(d, seed) if d % RICH_EVERY == 0 else None
        sent = 0
        for w in range(WAVES):
            ops = [(TEXT, op) for op in
                   text[w * TEXT_OPS:(w + 1) * TEXT_OPS]]
            if rich is not None:
                ops += rich[w]
            msgs = []
            ref = sent + 1 if w else 0
            if w == 0:
                msgs.append(DocumentMessage(
                    0, -1, MessageType.CLIENT_JOIN,
                    data=json.dumps({"clientId": client, "detail": {}})))
            for chan, op in ops:
                sent += 1
                msgs.append(DocumentMessage(
                    client_sequence_number=sent,
                    reference_sequence_number=ref,
                    type=MessageType.OPERATION,
                    contents={"address": STORE, "contents": {
                        "address": chan, "contents": op}}))
            waves[w].append(QueuedMessage(
                topic="rawdeltas", partition=0, offset=offset, key=doc,
                value=boxcar_to_wire(Boxcar(
                    tenant_id="t", document_id=doc, client_id=client,
                    contents=msgs))))
            offset += 1
    return waves


class _Ctx:
    def checkpoint(self, *_):
        pass

    def error(self, err, restart=False):
        raise err


def _stream_recorder():
    streams = {}

    def record(doc, msg):
        streams.setdefault(doc, []).append(
            (msg.sequence_number, msg.minimum_sequence_number,
             msg.client_id, msg.client_sequence_number, msg.type))
    return streams, record


def serve(waves, mesh=None):
    """Feed the waves through TpuSequencerLambda.handler_raw, pipelined,
    flushing each wave and draining only at the end."""
    from fluidframework_tpu.server.tpu_sequencer import TpuSequencerLambda

    streams, record = _stream_recorder()
    nacks = []
    lam = TpuSequencerLambda(_Ctx(), emit=record,
                             nack=lambda *a: nacks.append(a),
                             client_timeout_s=0.0, mesh=mesh)
    lam.pipelined = True
    for wave in waves:
        for qm in wave:
            lam.handler_raw(qm)
        lam.flush()
    lam.drain()
    check(not nacks, f"served path nacked {len(nacks)} ops")
    return lam, streams


def scalar_deli(waves):
    from fluidframework_tpu.server.lambdas.deli import DeliLambda

    streams, record = _stream_recorder()
    sequenced = {}

    def keep(doc, msg):
        record(doc, msg)
        sequenced.setdefault(doc, []).append(msg)

    deli = DeliLambda(_Ctx(), emit=keep, nack=lambda *a: None,
                      config={"deli.clientTimeoutMsec": 0})
    for wave in waves:
        for qm in wave:
            deli.handler(qm)
    return streams, sequenced


def oracle_channels(messages):
    """Replay one doc's scalar-sequenced stream through the client-side
    replicas: MergeTreeClient for the text, SharedMatrix and
    SharedDirectory for the rich channels."""
    from fluidframework_tpu.dds.directory import SharedDirectory
    from fluidframework_tpu.dds.matrix import SharedMatrix
    from fluidframework_tpu.mergetree.client import MergeTreeClient
    from fluidframework_tpu.protocol.messages import MessageType

    text = MergeTreeClient(client_id=-1)
    grid = SharedMatrix(GRID)
    tree = SharedDirectory(DIR)
    for m in messages:
        if m.type != MessageType.OPERATION:
            continue
        envelope = m.contents["contents"]
        chan, op = envelope["address"], envelope["contents"]
        args = (m.sequence_number, m.reference_sequence_number, 1)
        if chan == TEXT:
            text.apply_msg(op, *args, min_seq=m.minimum_sequence_number)
        else:
            (grid if chan == GRID else tree).process(
                op, False, *args, m.minimum_sequence_number)
    return text.get_text(), grid.extract(), tree.root.to_dict()


def _sample_docs(docs: int, seed: int):
    rng = random.Random(seed)
    rich = [d for d in range(docs) if d % RICH_EVERY == 0]
    plain = [d for d in range(docs) if d % RICH_EVERY]
    return sorted(rng.sample(rich, min(SAMPLE, len(rich)))
                  + rng.sample(plain, min(SAMPLE, len(plain))))


def _serving_faults(base=None) -> dict:
    """Fallback and degrade counts since the ``base`` snapshot."""
    from fluidframework_tpu.telemetry import counters

    snap = counters.snapshot()
    base = base or {}
    faults = {name: snap.get(name, 0.0) - base.get(name, 0.0) for name in (
        "serving.megakernel_fallbacks",)}
    faults.update({k: v - base.get(k, 0.0) for k, v in snap.items()
                   if v != base.get(k, 0.0) and (
        k.startswith("swallowed.pallas.") or k in (
            "swallowed.oppack.native_fallback",
            "swallowed.sequencer.pump_unavailable",
            "swallowed.pump.unavailable"))})
    return faults


def served_phase(docs: int = DOCS, seed: int = SEED,
                 expect_fused=True) -> dict:
    """The 10k-doc mixed-DDS fleet through the raw fast path, against
    scalar DeliLambda sequencing and client-side replica state."""
    from fluidframework_tpu.mergetree.oppack import _native_pack
    from fluidframework_tpu.server import pump
    from fluidframework_tpu.telemetry import counters

    check(pump.available(), "native wire pump not loaded")
    check(_native_pack() is not None, "native op packer not loaded")
    t0 = time.perf_counter()
    waves = served_traffic(docs, seed)
    build_s = time.perf_counter() - t0

    # Deltas, not a reset: the compile counters run across phases.
    base = counters.snapshot()
    t0 = time.perf_counter()
    lam, streams = serve(waves)
    serve_s = time.perf_counter() - t0
    rings = counters.get("serving.megakernel_rings") \
        - base.get("serving.megakernel_rings", 0.0)
    ring_windows = counters.get("serving.megakernel_windows") \
        - base.get("serving.megakernel_windows", 0.0)
    mode = lam._mega_fused_mode()
    check(mode == expect_fused,
          f"megakernel op-phase mode {mode!r}, expected {expect_fused!r}")
    faults = _serving_faults(base)
    check(not any(faults.values()), f"fallbacks fired: {faults}")
    check(rings > 0, "no serving megakernel ring was dispatched")

    t0 = time.perf_counter()
    deli_streams, sequenced = scalar_deli(waves)
    deli_s = time.perf_counter() - t0
    check(sorted(streams) == sorted(deli_streams),
          "served and scalar doc sets differ")
    bad = [d for d in deli_streams if streams[d] != deli_streams[d]]
    check(not bad, f"{len(bad)} docs sequenced differently, e.g. {bad[:3]}")
    n_msgs = sum(len(s) for s in streams.values())

    picked = _sample_docs(docs, seed)
    for d in picked:
        doc = f"doc{d}"
        text, grid, tree = oracle_channels(sequenced[doc])
        check(lam.channel_text(doc, STORE, TEXT) == text,
              f"{doc}: served text != oracle")
        if d % RICH_EVERY == 0:
            check(lam.channel_matrix(doc, STORE, GRID) == grid,
                  f"{doc}: served matrix != oracle")
            check(lam.channel_directory(doc, STORE, DIR) == tree,
                  f"{doc}: served directory != oracle")
    return {"docs": docs, "rich_docs": len(range(0, docs, RICH_EVERY)),
            "waves": WAVES, "messages": n_msgs, "docs_checked": len(picked),
            "megakernel_mode": mode, "megakernel_rings": rings,
            "megakernel_windows": ring_windows, "faults": faults,
            "traffic_build_s": build_s, "serve_s": serve_s,
            "scalar_deli_s": deli_s,
            "smoke_served_msgs_per_s": n_msgs / serve_s}


# ---------------------------------------------------------------------------
# phase 4: summarize + catch-up through the loader
# ---------------------------------------------------------------------------

def dispatch_seconds(capacity: int, reps: int = 20) -> dict:
    """Median wall time of one catch-up chunk dispatch at B=1 (kernel
    apply + the host overflow fetch, as catchup.apply_host_ops pays it per
    chunk) at T=1 and at the full CHUNK_T: the fixed cost behind
    costmodel.TPU_DISPATCH_S, and the per-op slope."""
    import numpy as np

    from fluidframework_tpu.mergetree import kernel
    from fluidframework_tpu.mergetree.catchup import (CHUNK_T,
                                                      seed_device_state)
    from fluidframework_tpu.mergetree.host import OpBuilder
    from fluidframework_tpu.mergetree.oppack import pack_single

    median = {}
    for t in (1, CHUNK_T):
        builder = OpBuilder()
        packed = pack_single([builder.insert_text(0, "x", i, 1, i + 1)
                              for i in range(t)], steps=t)
        state = seed_device_state([], builder.payloads, capacity, 0, 0)
        times = []
        for i in range(reps + 2):  # two warm-ups
            t0 = time.perf_counter()
            bool(np.asarray(kernel.apply_ops_keep(state, packed,
                                                  None).overflow))
            times.append(time.perf_counter() - t0)
        median[t] = sorted(times[2:])[reps // 2]
    return {"dispatch_s": median[1], "catchup_capacity": capacity,
            "per_op_s": (median[CHUNK_T] - median[1]) / (CHUNK_T - 1)}


def loader_phase(min_segments: int = 1000, tail_ops: int = 3000,
                 seed: int = SEED, dispatch_reps: int = 20) -> dict:
    from fluidframework_tpu.dds.directory import SharedDirectory
    from fluidframework_tpu.dds.sequence import SharedString
    from fluidframework_tpu.loader.container import Loader
    from fluidframework_tpu.loader.drivers.local import (
        LocalDocumentServiceFactory)
    from fluidframework_tpu.mergetree.catchup import (CAPACITY_BUCKETS,
                                                      CHUNK_T)
    from fluidframework_tpu.server.local_server import TpuLocalServer
    from fluidframework_tpu.telemetry import counters

    server = TpuLocalServer()
    loader = Loader(LocalDocumentServiceFactory(server))
    c1 = loader.create_detached("doc")
    ds = c1.runtime.create_datastore("default")
    c1.attach()
    t1 = ds.create_channel("text", SharedString.TYPE)
    # A directory beside the text, as on config #5's rich docs: the read
    # tier publishes no delta artifact for a doc with an LWW lane, so a
    # late client replays the op tail — the bulk catch-up path.
    d1 = ds.create_channel("dir", SharedDirectory.TYPE)
    d1.set("title", "load test")
    c2 = loader.resolve("doc")
    t2 = c2.runtime.get_datastore("default").get_channel("text")
    rng = random.Random(seed)
    edits = 0

    def edit():
        nonlocal edits
        who = (t1, t2)[edits % 2]
        n = who.get_length()
        if n > 8 and rng.random() < 0.2:
            a = rng.randrange(n - 4)
            who.remove_text(a, a + rng.randrange(1, 4))
        else:
            who.insert_text(rng.randrange(n + 1), f"<{edits}>",
                            {"e": edits})
        edits += 1

    t0 = time.perf_counter()
    while len(t1.client.tree.segments) < min_segments:
        edit()
    check(t1.get_text() == t2.get_text(), "editing clients diverged")
    segments = len(t1.client.tree.segments)
    c1.summarize()
    seq = server.sequencer()
    first = seq.summarize_documents()
    hits0 = counters.get("summarize.blob_cache.hits")
    misses0 = counters.get("summarize.blob_cache.misses")
    second = seq.summarize_documents()
    check(second == first, "second summarize pass differs")
    check(counters.get("summarize.blob_cache.misses") == misses0,
          "second summarize pass missed the blob cache")
    merge_keys = [k for k in second if k in seq.merge.where]
    check(merge_keys, "no merge lane was summarized")
    check(counters.get("summarize.blob_cache.hits") - hits0
          >= len(merge_keys), "second summarize pass was not all cache hits")
    for _ in range(tail_ops):
        edit()
    edit_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    late = loader.resolve("doc")
    t3 = late.runtime.get_datastore("default").get_channel("text")
    check(t3.get_text() == t1.get_text(), "late client text differs")
    resolve_s = time.perf_counter() - t0
    check(seq.channel_text("doc", "default", "text") == t1.get_text(),
          "server-side text differs from the clients")
    check(t3.bulk_catchup_count >= 1,
          "late client caught up on the scalar path, not the device")
    # The catch-up capacity bucket this tail's chunks ran at.
    capacity = next(c for c in CAPACITY_BUCKETS
                    if c >= segments + 2 * CHUNK_T + 8)
    return {"live_segments_at_summary": segments, "edits": edits,
            "tail_ops": tail_ops, "summary_channels": len(second),
            "bulk_catchup_count": t3.bulk_catchup_count,
            "edit_s": edit_s, "late_resolve_s": resolve_s,
            **dispatch_seconds(capacity, dispatch_reps)}


# ---------------------------------------------------------------------------
# four chips: the dp-sharded served path and full_step on dp x sp
# ---------------------------------------------------------------------------

def _check_quartered(tree, n_dev: int, what: str) -> int:
    """Every leaf with a lane axis spans all devices, one 1/n_dev slice of
    its lanes on each."""
    import jax

    leaves = [x for x in jax.tree_util.tree_leaves(tree)
              if hasattr(x, "sharding") and x.ndim]
    for x in leaves:
        check(len(x.sharding.device_set) == n_dev,
              f"{what}: a {x.shape} plane spans "
              f"{len(x.sharding.device_set)} devices")
        rows = {s.data.shape[0] for s in x.addressable_shards}
        check(rows == {x.shape[0] // n_dev},
              f"{what}: a {x.shape} plane splits its lanes as {rows}")
    return len(leaves)


def mesh_served_phase(docs: int = DOCS, seed: int = SEED, dp: int = 4):
    from fluidframework_tpu.parallel.mesh import make_mesh
    from fluidframework_tpu.testing import shardcheck

    import jax

    mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
    waves = served_traffic(docs, seed)
    t0 = time.perf_counter()
    lam, streams = serve(waves, mesh=mesh)
    mesh_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    one, one_streams = serve(waves)
    one_s = time.perf_counter() - t0
    faults = _serving_faults()
    check(not any(faults.values()), f"fallbacks fired: {faults}")
    bad = [d for d in one_streams if streams.get(d) != one_streams[d]]
    check(not bad and len(streams) == len(one_streams),
          f"{len(bad)} docs sequenced differently on the mesh")
    for d in _sample_docs(docs, seed):
        doc = f"doc{d}"
        check(lam.channel_text(doc, STORE, TEXT)
              == one.channel_text(doc, STORE, TEXT),
              f"{doc}: mesh text != one-device text")
        if d % RICH_EVERY == 0:
            check(lam.channel_matrix(doc, STORE, GRID)
                  == one.channel_matrix(doc, STORE, GRID),
                  f"{doc}: mesh matrix != one-device matrix")
            check(lam.channel_directory(doc, STORE, DIR)
                  == one.channel_directory(doc, STORE, DIR),
                  f"{doc}: mesh directory != one-device directory")
    check(len(lam.tstate.next_seq.sharding.device_set) == dp,
          "ticket lanes do not span the mesh")
    planes = _check_quartered(lam.tstate, dp, "ticket state")
    planes += _check_quartered(lam.merge.pages.pool, dp, "page pool")
    planes += _check_quartered([b.state for b in lam.lww.buckets], dp,
                               "lww lanes")
    report = shardcheck.placement_report(lam.merge, mesh)
    check(report["ok"], f"page pool placement: {report.get('error')}")
    return {"dp": dp, "docs": docs, "planes_checked": planes,
            "mesh_serve_s": mesh_s, "one_device_serve_s": one_s,
            "shardcheck_leaves": report["checked"]}


def mesh_kernel_phase(docs: int = DOCS, n_ops: int = 100,
                      capacity: int = 256, seed: int = SEED) -> dict:
    import jax
    import numpy as np

    from fluidframework_tpu.parallel.mesh import make_mesh, shard_docs
    from fluidframework_tpu.server.pipeline import make_full_step

    _, raw, ops = kernel_inputs(docs, n_ops, seed)
    tstate, mstate = fresh_states(docs, capacity)
    ref = jax.jit(make_full_step(fused_apply=True))(tstate, mstate, raw,
                                                      ops)
    mesh = make_mesh(dp=2, sp=2, devices=jax.devices()[:4])
    args = (shard_docs(mesh, tstate),
            shard_docs(mesh, mstate, seq_sharded=True),
            shard_docs(mesh, raw), shard_docs(mesh, ops))
    step = jax.jit(make_full_step(sp_shards=2, fused_apply=True, mesh=mesh)
                   ).lower(*args).compile()
    # The Pallas summary runs per shard: its kernel is in the program.
    check(jax.default_backend() != "tpu"
          or "tpu_custom_call" in step.as_text(),
          "dp x sp full_step carries no Pallas kernel")
    out = step(*args)
    check(len(out[1].length.sharding.device_set) == 4,
          "dp x sp state does not span 4 devices")
    same = jax.tree_util.tree_map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
        (ref[1], ref[3]), (out[1], out[3]))
    check(all(jax.tree_util.tree_leaves(same)),
          "dp=2 x sp=2 full_step differs from dp=1")
    return {"docs": docs, "ops_per_doc": n_ops, "capacity": capacity,
            "mesh": {"dp": 2, "sp": 2}}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    # Routing must reach the device on its own (phase 4): no override.
    os.environ.pop("FLUID_TPU_FORCE_BULK", None)
    try:
        device = device_phase(args.chips)
        from fluidframework_tpu.telemetry.compile_ledger import \
            install_jax_listener

        install_jax_listener()
        emit({"phase": "device", "ok": True, **device})
        if args.chips == 4:
            phases = [("mesh_served", mesh_served_phase),
                      ("mesh_kernel", mesh_kernel_phase)]
        else:
            phases = [("kernel", kernel_phase), ("served", served_phase),
                      ("loader", loader_phase)]
        for name, run in phases:
            mark = compile_mark()
            try:
                result = run()
            except Exception as err:
                emit({"phase": name, "ok": False,
                      "error": f"{type(err).__name__}: {err}"[:2000],
                      **compile_since(mark)})
                raise
            emit({"phase": name, "ok": True, **compile_since(mark), **result})
    except Exception as err:  # noqa: BLE001 — any failure fails the smoke
        print(f"chip_smoke: FAILED: {type(err).__name__}: {err}",
              file=sys.stderr)
        return 1
    emit({"ok": True, "device": {"platform": device["platform"],
                                 "kind": device["kind"],
                                 "count": device["count"]}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
