"""Opt-in randomized differential soaks (SOAK=1; trials scale with
SOAK_TRIALS). Deeper than the fixed-seed suites: random burst schedules
with pendings/items through bulk catch-up vs the scalar oracle, and
mixed-boxcar traffic with random flush boundaries through the serving
fast path vs the object path. The chaos farms' role (SURVEY §5 race
detection) at the round-4 surfaces."""

import json
import os
import random

import pytest

from fluidframework_tpu.mergetree.client import (
    MergeTreeClient,
    OP_ANNOTATE,
    OP_INSERT,
    OP_REMOVE,
    items_seg,
    make_annotate_op,
    make_insert_op,
    make_remove_op,
    text_seg,
)
from fluidframework_tpu.protocol.messages import (
    Boxcar,
    DocumentMessage,
    MessageType,
)

# Randomized soaks stay opt-in; the fixed-seed chaos scenarios at the
# bottom (reconnect avalanche, hot document) are deterministic and run
# in tier-1 unconditionally.
soak = pytest.mark.skipif(
    os.environ.get("SOAK") != "1",
    reason="randomized soak; set SOAK=1 (SOAK_TRIALS to scale)")

TRIALS = int(os.environ.get("SOAK_TRIALS", "10"))


def _flat(cl):
    out = []
    tree = cl.tree
    for seg in tree.segments:
        if tree.visible_length(seg, tree.current_seq, cl.client_id) > 0:
            payload = seg.text
            vals = (payload.values if hasattr(payload, "values")
                    else payload)
            props = dict(seg.props) if seg.props else None
            out.extend((v, props) for v in vals)
    return out


def _burst_schedule(rng, n_ops, n_clients=3):
    auth = MergeTreeClient(client_id=-1)
    tail = []
    seq = 0
    cursors = {c: 0 for c in range(1, n_clients + 1)}
    while len(tail) < n_ops:
        c = rng.randrange(1, n_clients + 1)
        if rng.random() < 0.6:  # typing burst, frozen ref
            ref = seq
            cur = min(cursors[c], auth.get_length())
            for _ in range(rng.randrange(2, 14)):
                seq += 1
                op = make_insert_op(cur,
                                    text_seg(chr(97 + rng.randrange(26))))
                auth.apply_msg(op, seq, ref, c, min_seq=max(0, seq - 40))
                tail.append((op, seq, ref, c, max(0, seq - 40)))
                cur += 1
            cursors[c] = cur
            continue
        n = auth.get_length()
        seq += 1
        roll = rng.random()
        if n > 6 and roll < 0.4:
            a = rng.randrange(n - 1)
            op = make_remove_op(a, min(n, a + rng.randrange(1, 6)))
        elif n > 3 and roll < 0.6:
            a = rng.randrange(n - 1)
            op = make_annotate_op(a, a + 1, {"k": seq % 5})
        elif roll < 0.8 and n > 0:
            op = make_insert_op(rng.randrange(n + 1),
                                items_seg([seq, seq + 1]))
        else:
            op = make_insert_op(rng.randrange(n + 1) if n else 0,
                                text_seg(f"[{seq}]"))
        auth.apply_msg(op, seq, seq - 1, c, min_seq=max(0, seq - 40))
        tail.append((op, seq, seq - 1, c, max(0, seq - 40)))
    return tail


@soak
class TestBulkCatchupSoak:
    @pytest.mark.parametrize("trial", range(TRIALS))
    def test_random_burst_schedules_match_scalar(self, trial):
        rng = random.Random(10_000 + trial)
        tail = _burst_schedule(rng, rng.randrange(100, 500))
        split = rng.randrange(0, len(tail) // 2) if rng.random() < 0.5 \
            else 0
        head, rest = tail[:split], tail[split:]
        bulk = MergeTreeClient(client_id=99)
        scalar = MergeTreeClient(client_id=99)
        for op, s, r, c, m in head:
            bulk.apply_msg(op, s, r, c, min_seq=m)
            scalar.apply_msg(op, s, r, c, min_seq=m)
        if split and rng.random() < 0.7:
            n = bulk.get_length()
            pos = rng.randrange(n + 1) if n else 0
            for cl in (bulk, scalar):
                cl.insert_text_local(pos, "PEND")
            if rng.random() < 0.5 and bulk.get_length() > 6:
                for cl in (bulk, scalar):
                    cl.remove_range_local(1, 4)
        from fluidframework_tpu.mergetree.catchup import Unmodelable
        try:
            bulk.apply_bulk(rest)
        except Unmodelable:
            # Legitimate fallback shape: still differential — apply the
            # tail scalar on BOTH replicas so the trial asserts equality
            # instead of going vacuous.
            for op, s, r, c, m in rest:
                bulk.apply_msg(op, s, r, c, min_seq=m)
        for op, s, r, c, m in rest:
            scalar.apply_msg(op, s, r, c, min_seq=m)
        assert _flat(bulk) == _flat(scalar)
        if bulk.tree.pending_groups:
            assert bulk.regenerate_pending_ops() == \
                scalar.regenerate_pending_ops()


def _join(cid):
    return DocumentMessage(0, -1, MessageType.CLIENT_JOIN,
                           data=json.dumps({"clientId": cid,
                                            "detail": {}}))


def _serving_traffic(rng, docs=3):
    boxes = []
    for d in range(docs):
        doc = f"d{d}"
        csn = {}
        lens = 0
        for bx in range(rng.randrange(1, 4)):
            cid = f"c{d}.{bx % 2}"
            msgs = []
            if cid not in csn:
                msgs.append(_join(cid))
                csn[cid] = 0
            ref = rng.randrange(0, 30)
            pos = rng.randrange(lens + 1) if lens else 0
            prepend = rng.random() < 0.4
            for i in range(rng.randrange(3, 20)):
                csn[cid] += 1
                roll = rng.random()
                if roll < 0.75:
                    text = chr(97 + rng.randrange(26)) * rng.randrange(1, 3)
                    op = {"type": OP_INSERT, "pos1": pos,
                          "seg": {"text": text}}
                    if not prepend:
                        pos += len(text)
                    lens += len(text)
                elif roll < 0.88 and lens > 4:
                    a = rng.randrange(lens - 2)
                    b = min(lens, a + rng.randrange(1, 4))
                    op = {"type": OP_REMOVE, "pos1": a, "pos2": b}
                    lens -= b - a
                    pos = min(pos, lens)
                else:
                    if lens < 2:
                        continue
                    a = rng.randrange(lens - 1)
                    op = {"type": OP_ANNOTATE, "pos1": a, "pos2": a + 1,
                          "props": {"w": i}}
                msgs.append(DocumentMessage(
                    client_sequence_number=csn[cid],
                    reference_sequence_number=ref,
                    type=MessageType.OPERATION,
                    contents={"address": "s", "contents": {
                        "address": "t", "contents": op}}))
            boxes.append((doc, Boxcar("t", doc, cid, msgs)))
    return boxes


@soak
class TestServingSoak:
    @pytest.mark.parametrize("trial", range(TRIALS))
    def test_random_boxcars_fast_matches_object(self, trial):
        from fluidframework_tpu.server import pump as pump_mod
        if not pump_mod.available():
            pytest.skip("native wirepump unavailable")
        from fluidframework_tpu.server.log import QueuedMessage
        from fluidframework_tpu.server.tpu_sequencer import (
            TpuSequencerLambda)
        from fluidframework_tpu.server.wire import boxcar_to_wire

        class _Ctx:
            def checkpoint(self, *_):
                pass

            def error(self, err, restart=False):
                raise err

        def key(doc_id, m):
            return (doc_id, m.sequence_number, m.minimum_sequence_number,
                    m.type, m.client_id, m.client_sequence_number,
                    m.reference_sequence_number,
                    json.dumps(m.contents, sort_keys=True), m.data)

        rng = random.Random(55_000 + trial)
        ea, eb, na, nb = [], [], [], []
        A = TpuSequencerLambda(
            _Ctx(), emit=lambda d, m: ea.append(key(d, m)),
            nack=lambda d, c, n: na.append((d, c, n.content.code)),
            client_timeout_s=0.0)
        B = TpuSequencerLambda(
            _Ctx(), emit=lambda d, m: eb.append(key(d, m)),
            nack=lambda d, c, n: nb.append((d, c, n.content.code)),
            client_timeout_s=0.0)
        # Half the trials run the fast path through the pipelined
        # megakernel ring (docs/serving_pipeline.md R10) — random burst
        # schedules are exactly where ring reordering or a stale-lane
        # staging bug would surface as a diff.
        B.pipelined = trial % 2 == 1
        # Runtime lockset verification (fluidlint v3's dynamic half):
        # the pipelined store runs the soak with the statically inferred
        # summarize-guard discipline asserted on every access.
        from fluidframework_tpu.testing.lockcheck import (instrument,
                                                          static_guards)
        guards = static_guards(type(B.merge))
        guards["_deferred_frees"] = "_guard_lock"
        lockcheck = instrument(B.merge, guards)
        try:
            tr = _serving_traffic(rng)
            for i, (doc, box) in enumerate(tr):
                A.handler(QueuedMessage("rawdeltas", 0, i, doc, box))
                B.handler_raw(QueuedMessage("rawdeltas", 0, i, doc,
                                            boxcar_to_wire(box)))
                if rng.random() < 0.3:
                    A.flush()
                    B.flush()
            A.flush()
            B.flush()
            A.drain()
            B.drain()
            lockcheck.assert_clean()
        finally:
            lockcheck.uninstrument()
        assert sorted(ea) == sorted(eb)
        assert sorted(na) == sorted(nb)
        for d in {t[0] for t in tr}:
            assert A.channel_text(d, "s", "t") == \
                B.channel_text(d, "s", "t"), d


@soak
class TestMeshPlacementSoak:
    """fluidlint v4's dynamic half on the real serving path: random
    sessions against a PAGED dp-mesh sequencer with the runtime
    shardcheck (testing/shardcheck.py) asserting every device-resident
    plane against the partition-rule table mid-traffic — the MAY
    placements the static pass deliberately skips get verified here
    while the code actually runs."""

    @pytest.mark.parametrize("trial", range(max(1, TRIALS // 5)))
    def test_paged_mesh_placements_hold_under_traffic(self, trial):
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-virtual-device mesh")
        from fluidframework_tpu.dds.sequence import SharedString
        from fluidframework_tpu.parallel.mesh import make_mesh
        from fluidframework_tpu.server.local_server import TpuLocalServer
        from fluidframework_tpu.testing import shardcheck

        rng = random.Random(91_000 + trial)
        mesh = make_mesh(sp=1)
        server, loader, chans = _soak_session(
            SharedString.TYPE,
            server_cls=lambda: TpuLocalServer(mesh=mesh))
        checked = 0
        for _ in range(20):
            ch = rng.choice(chans)
            pos = rng.randrange(ch.get_length() + 1)
            ch.insert_text(pos, rng.choice("abcdef") * rng.randint(1, 3))
            if rng.random() < 0.3:
                checked += shardcheck.verify_store(
                    server.sequencer().merge, mesh)
        checked += shardcheck.verify_store(server.sequencer().merge,
                                           mesh)
        assert checked > 0
        assert len({c.get_text() for c in chans}) == 1


def _soak_session(channel_type, server_cls=None, n_clients=2):
    """One session bring-up for every soak class: server + loader + N
    channel replicas."""
    from fluidframework_tpu.loader.container import Loader
    from fluidframework_tpu.loader.drivers.local import (
        LocalDocumentServiceFactory)
    from fluidframework_tpu.server.local_server import (LocalServer,
                                                        TpuLocalServer)

    server = (server_cls or TpuLocalServer)()
    loader = Loader(LocalDocumentServiceFactory(server))
    c1 = loader.create_detached("doc")
    ds = c1.runtime.create_datastore("default")
    channels = [ds.create_channel("ch", channel_type)]
    c1.attach()
    for _ in range(n_clients - 1):
        c = loader.resolve("doc")
        channels.append(c.runtime.get_datastore("default")
                        .get_channel("ch"))
    return server, loader, channels


@soak
class TestMatrixServingSoak:
    """Round-5 surface: SharedMatrix device serving lanes under random
    concurrent sessions with mid-session sequencer restarts."""

    @pytest.mark.parametrize("trial", range(TRIALS))
    def test_random_matrix_sessions_match(self, trial):
        from fluidframework_tpu.dds.matrix import SharedMatrix

        rng = random.Random(91_000 + trial)
        server, _, (m1, m2) = _soak_session(SharedMatrix.TYPE)
        for step in range(rng.randrange(40, 120)):
            m = rng.choice([m1, m2])
            r, c = m.row_count, m.col_count
            act = rng.random()
            if act < 0.25 or r == 0:
                m.insert_rows(rng.randint(0, r), rng.randint(1, 3))
            elif act < 0.5 or c == 0:
                m.insert_cols(rng.randint(0, c), rng.randint(1, 2))
            elif act < 0.6 and r > 1:
                m.remove_rows(rng.randrange(r - 1), 1)
            elif act < 0.65 and c > 1:
                m.remove_cols(rng.randrange(c - 1), 1)
            else:
                m.set_cell(rng.randrange(r), rng.randrange(c), step)
            if rng.random() < 0.02:
                server._deli_mgr.restart()
        assert m1.extract() == m2.extract()
        grid = server.sequencer().channel_matrix("doc", "default", "ch")
        assert grid == m1.extract()


@soak
class TestDirectoryServingSoak:
    """Round-5 surface: SharedDirectory LWW lane + path-set gating under
    random nested sessions with restarts."""

    @pytest.mark.parametrize("trial", range(TRIALS))
    def test_random_directory_sessions_match(self, trial):
        from fluidframework_tpu.dds.directory import SharedDirectory

        rng = random.Random(93_000 + trial)
        server, _, (d1, d2) = _soak_session(SharedDirectory.TYPE)
        names = ["a", "b", "c"]
        for step in range(rng.randrange(60, 160)):
            d = rng.choice([d1, d2])
            paths = ["/"]
            for n1 in names:
                if d.get_working_directory("/" + n1) is not None:
                    paths.append("/" + n1)
                    for n2 in names:
                        if d.get_working_directory(
                                f"/{n1}/{n2}") is not None:
                            paths.append(f"/{n1}/{n2}")
            path = rng.choice(paths)
            wd = d.root if path == "/" else d.get_working_directory(path)
            act = rng.random()
            if act < 0.15 and path.count("/") < 3:
                wd.create_sub_directory(rng.choice(names))
            elif act < 0.22 and path != "/":
                parent, _, name = path.rpartition("/")
                pd = d.root if not parent else \
                    d.get_working_directory(parent)
                if pd is not None:
                    pd.delete_sub_directory(name)
            elif act < 0.28:
                wd.clear()
            elif act < 0.4:
                wd.delete(f"k{rng.randrange(4)}")
            else:
                wd.set(f"k{rng.randrange(4)}", step)
            if rng.random() < 0.02:
                server._deli_mgr.restart()
        assert d1.root.to_dict() == d2.root.to_dict()
        tree = server.sequencer().channel_directory("doc", "default", "ch")
        assert tree == d1.root.to_dict()


@soak
class TestIntervalCatchupSoak:
    """Round-5 surface: interval ops interleaved with merge history
    through the run-splitting bulk catch-up."""

    @pytest.mark.parametrize("trial", range(TRIALS))
    def test_random_interval_histories_catch_up(self, trial):
        from fluidframework_tpu.dds.sequence import SharedString
        from fluidframework_tpu.server.local_server import LocalServer

        rng = random.Random(95_000 + trial)
        server, loader, (text,) = _soak_session(
            SharedString.TYPE, server_cls=LocalServer, n_clients=1)
        ic = text.get_interval_collection("marks")
        ids = []
        for i in range(rng.randrange(80, 200)):
            n = text.get_length()
            act = rng.random()
            if act < 0.6 or n < 8:
                text.insert_text(rng.randrange(n + 1) if n else 0,
                                 f"[{i % 10}]")
            elif act < 0.8:
                a = rng.randrange(n - 2)
                text.remove_text(a, min(n, a + rng.randrange(1, 4)))
            elif act < 0.9 and n > 4:
                iv = ic.add(rng.randrange(n - 2), rng.randrange(2, n),
                            {"i": i})
                ids.append(iv.interval_id)
            elif ids:
                iid = rng.choice(ids)
                if rng.random() < 0.5 and text.get_length() > 4:
                    ic.change(iid, 1, text.get_length() - 1)
                else:
                    ic.remove_interval_by_id(iid)
                    ids.remove(iid)
        late = loader.resolve("doc")
        t2 = late.runtime.get_datastore("default").get_channel("ch")
        assert t2.get_text() == text.get_text()
        lc = t2.get_interval_collection("marks")
        assert len(lc) == len(ic)
        src = {iv.interval_id: ic.endpoints(iv) for iv in ic}
        got = {iv.interval_id: lc.endpoints(iv) for iv in lc}
        assert got == src


@soak
class TestItemsServingSoak:
    """Round-5 surface: item sequences materialized on server merge
    lanes, under random two-client sessions with restarts."""

    @pytest.mark.parametrize("trial", range(TRIALS))
    def test_random_items_sessions_match(self, trial):
        from fluidframework_tpu.dds.sequence import SharedNumberSequence

        rng = random.Random(97_000 + trial)
        server, _, (s1, s2) = _soak_session(SharedNumberSequence.TYPE)
        for step in range(rng.randrange(50, 140)):
            s = rng.choice([s1, s2])
            n = s.get_item_count()
            r = rng.random()
            if r < 0.65 or n < 6:
                s.insert_range(rng.randrange(n + 1),
                               [step, step + 0.5])
            elif r < 0.9:
                a = rng.randrange(n - 2)
                s.remove_range(a, min(n, a + rng.randrange(1, 4)))
            else:
                a = rng.randrange(n - 2)
                s.annotate_range(a, a + 2, {"fmt": step % 3})
            if rng.random() < 0.02:
                server._deli_mgr.restart()
        assert s1.get_items() == s2.get_items()
        items = server.sequencer().channel_items("doc", "default", "ch")
        assert items == s1.get_items()


@soak
class TestWireFuzzSoak:
    """The round-5 native parse paths (matrix envelope, directory
    storage, run arrays) under random byte corruption: the pump must
    never crash the lambda — corrupt frames route slow or surface as
    contained errors, and uncorrupted traffic still matches the object
    path afterward."""

    @pytest.mark.parametrize("trial", range(TRIALS))
    def test_corrupted_frames_never_crash_the_pump(self, trial):
        from fluidframework_tpu.server import pump as pump_mod
        if not pump_mod.available():
            pytest.skip("native wirepump unavailable")
        from fluidframework_tpu.server.log import QueuedMessage
        from fluidframework_tpu.server.tpu_sequencer import (
            TpuSequencerLambda)
        from fluidframework_tpu.server.wire import boxcar_to_wire

        class _Ctx:
            def checkpoint(self, *_):
                pass

            def error(self, err, restart=False):
                raise err

        rng = random.Random(99_000 + trial)
        nonce = (1 << 44) + trial
        lam = TpuSequencerLambda(_Ctx(), emit=lambda *a: None,
                                 nack=lambda *a: None,
                                 client_timeout_s=0.0)

        def boxcar(doc, msgs, cid):
            return boxcar_to_wire(Boxcar("t", doc, cid, msgs))

        ops = [
            {"target": "rows", "op": {"type": 0, "pos1": 0,
                                      "seg": {"run": [nonce, 1, 0, 3]}}},
            {"target": "cell", "key": f"{nonce}.1.0|{nonce}.1.1",
             "value": {"v": trial}},
            {"type": "storage", "path": "/", "op": {
                "type": "set", "key": "k", "value": 1, "pid": 1}},
            {"type": "createSubDirectory", "path": "/", "name": "s"},
            {"type": 0, "pos1": 0, "seg": {"items": [trial, "v", None]}},
        ]
        join = DocumentMessage(0, -1, MessageType.CLIENT_JOIN,
                               data=json.dumps({"clientId": "c",
                                                "detail": {}}))
        for i in range(40):
            op = rng.choice(ops)
            chan = ("g" if "target" in op
                    else "nums" if "seg" in op else "dir")
            msg = DocumentMessage(
                i + 1, i, MessageType.OPERATION,
                contents={"address": "s", "contents": {
                    "address": chan, "contents": op}})
            raw = boxcar("doc", [join, msg] if i == 0 else [msg], "c")
            if rng.random() < 0.5:  # corrupt: flip/truncate/insert bytes
                b = bytearray(raw)
                mode = rng.random()
                if mode < 0.4 and b:
                    for _ in range(rng.randrange(1, 4)):
                        b[rng.randrange(len(b))] = rng.randrange(256)
                elif mode < 0.7:
                    b = b[:rng.randrange(len(b))]
                else:
                    at = rng.randrange(len(b))
                    b[at:at] = bytes(rng.randrange(256)
                                     for _ in range(3))
                raw = bytes(b)
            try:
                lam.handler_raw(QueuedMessage("rawdeltas", 0, i, "doc",
                                              raw))
                if rng.random() < 0.3:
                    lam.flush()
            except Exception as err:  # noqa: BLE001
                # Contained per-frame errors are acceptable; native
                # crashes (segfault) would kill the process before this.
                assert not isinstance(err, (SystemError, MemoryError)), \
                    err
        lam.flush()
        lam.drain()
        # The lambda is still alive and serves clean traffic.
        ok = DocumentMessage(
            100, 99, MessageType.OPERATION,
            contents={"address": "s", "contents": {
                "address": "g", "contents": {
                    "target": "cell", "key": "a|b", "value": 1}}})
        lam.handler_raw(QueuedMessage("rawdeltas", 0, 999, "doc",
                                      boxcar("doc", [ok], "c")))
        lam.flush()
        lam.drain()


@soak
class TestMaintenanceSoak:
    """The serving maintenance machinery (page compaction, host rescue,
    block aging, payload-id collection) at its most hostile cadences — every knob at
    minimum — interleaved with restarts and async summaries. Any
    id/lane bookkeeping slip shows up as divergence from the client
    replicas or a crash."""

    @pytest.mark.parametrize("trial", range(TRIALS))
    def test_aggressive_maintenance_converges(self, trial):
        from fluidframework_tpu.dds.sequence import SharedString

        rng = random.Random(77_000 + trial)
        server, loader, chans = _soak_session(SharedString.TYPE,
                                              n_clients=2)

        def tune():
            st = server.sequencer().merge
            st.compact_every = 1
            st.block_age_ticks = 1
            st.payload_compact_every = rng.choice((1, 2))
            st.payload_compact_min_entries = 0
            st.compact_budget_per_tick = rng.choice((1, 4))
            return st

        store = tune()
        activity = 0  # accumulated across restarts (fresh store each)
        summaries = []

        def on_done(out):
            summaries.append(out)

        threads = []
        for i in range(rng.randrange(250, 400)):
            ch = rng.choice(chans)
            n = ch.get_length()
            if n > 8 and rng.random() < 0.3:
                start = rng.randrange(n - 4)
                ch.remove_text(start, start + rng.randrange(1, 4))
            elif n > 4 and rng.random() < 0.15:
                start = rng.randrange(n - 2)
                ch.annotate_range(start, start + 2, {"b": i % 3})
            else:
                ch.insert_text(rng.randrange(n + 1), f"m{i % 10}")
            if rng.random() < 0.05:
                threads.append(
                    server.sequencer().summarize_documents_async(on_done))
            if rng.random() < 0.02:
                # restart() rebuilds the lambda with a FRESH store:
                # bank the old one's counters and re-apply the hostile
                # knobs to the new one.
                activity += store.page_compactions \
                    + store.payload_compactions + store.blocks_aged
                server._deli_mgr.restart()
                store = tune()
        for th in threads:
            th.join(timeout=30)
        assert chans[0].get_text() == chans[1].get_text()
        assert server.sequencer().channel_text(
            "doc", "default", "ch") == chans[0].get_text()
        # Maintenance actually exercised (not silently gated off).
        activity += store.page_compactions + store.payload_compactions \
            + store.blocks_aged
        assert activity > 0


# ---------------------------------------------------------------------------
# Fixed-seed chaos scenarios (ROADMAP: reconnect avalanche, hot document)
# — deterministic by construction (testing/faultinject.py FaultPlan), so
# they run in tier-1 without the SOAK gate. Each scenario runs twice and
# must reproduce bit-identically from its seed.
# ---------------------------------------------------------------------------

from fluidframework_tpu.server.admission import (  # noqa: E402
    ACCEPT as ADM_ACCEPT,
    AdmissionController,
)
from fluidframework_tpu.server.local_server import LocalServer  # noqa: E402
from fluidframework_tpu.testing import faultinject  # noqa: E402


def _chaos_server(plan, queue_limit=512):
    """LocalServer with the fault injector on the raw ingest topic and a
    virtual-clocked admission controller at the front door."""
    vclock = {"t": 0.0}
    adm = AdmissionController(queue_limit=queue_limit,
                              recover_after_s=0.1, interval_s=0.005,
                              clock=lambda: vclock["t"])
    srv = LocalServer(auto_pump=False, admission=adm)
    srv.log = faultinject.FaultyMessageLog(srv.log, plan)
    return srv, adm, vclock


def _partial_pump(srv, limit):
    srv._deli_mgr.pumps[0].pump(limit=limit)
    for mgr in (srv._broadcaster_mgr, srv._scriptorium_mgr,
                srv._copier_mgr, srv._scribe_mgr):
        mgr.pump_all()


def _stable_cid(client_id):
    """client ids are `client-<counter>-<uuid8>`; the counter part is
    deterministic per run, the uuid suffix is not — strip it so two
    same-seed runs compare equal. System messages (joins/leaves
    sequenced server-side) carry no client id."""
    return client_id.rsplit("-", 1)[0] if client_id else None


class TestReconnectAvalancheChaos:
    """N clients on one document; the fault plan resets connections and
    drops/delays/dups raw deliveries. Every reset client reconnects in
    the SAME round (the avalanche) and resubmits whatever a stable
    observer has not yet seen sequenced. Convergence: every unique
    payload lands exactly once, both observers agree on the full
    stream, queues stay bounded, and two same-seed runs are
    bit-identical."""

    N_CLIENTS = 6
    ROUNDS = 25

    def _run(self, seed):
        plan = faultinject.FaultPlan(seed, drop=0.12, dup=0.12,
                                     delay=0.15, reset=0.12,
                                     max_delay_sends=4)
        srv, adm, vclock = _chaos_server(plan)
        obs_a = srv.connect("doc", {"mode": "read"})
        obs_b = srv.connect("doc", {"mode": "read"})
        seen_a, seen_b = [], []
        seen_payloads = set()

        def on_a(m):
            if m.type != MessageType.OPERATION:
                return
            seen_a.append((m.sequence_number, _stable_cid(m.client_id),
                           m.client_sequence_number))
            if isinstance(m.contents, dict) and "u" in m.contents:
                seen_payloads.add(m.contents["u"])

        obs_a.on("op", on_a)
        obs_b.on("op", lambda m: m.type == MessageType.OPERATION
                 and seen_b.append(
                     (m.sequence_number, _stable_cid(m.client_id),
                      m.client_sequence_number)))

        conns = {}
        csns = {}
        pending = {}  # client -> payload ids not yet confirmed
        for c in range(self.N_CLIENTS):
            conns[c] = srv.connect("doc")
            csns[c] = 0
            pending[c] = []
        srv.pump()

        def submit(c, uid):
            csns[c] += 1
            conns[c].submit([DocumentMessage(
                client_sequence_number=csns[c],
                reference_sequence_number=0,
                type=MessageType.OPERATION, contents={"u": uid})])

        uid = 0
        peak_backlog = 0
        for _ in range(self.ROUNDS):
            vclock["t"] += 0.02
            dropped = []
            for c in range(self.N_CLIENTS):
                uid += 1
                pending[c].append(uid)
                submit(c, uid)
                if plan.should_reset():
                    conns[c].disconnect()
                    dropped.append(c)
            peak_backlog = max(peak_backlog, srv.raw_backlog())
            _partial_pump(srv, limit=self.N_CLIENTS * 2)
            # The avalanche: every reset client reconnects at once and
            # resubmits everything not yet confirmed sequenced.
            for c in dropped:
                conns[c] = srv.connect("doc")
                csns[c] = 0
            srv.pump()
            for c in range(self.N_CLIENTS):
                pending[c] = [u for u in pending[c]
                              if u not in seen_payloads]
                if c in dropped:
                    for u in list(pending[c]):
                        submit(c, u)
            srv.pump()

        # Teardown: release delayed deliveries FIRST (so surviving
        # originals land before any final resubmission can duplicate a
        # payload under a fresh client id), then resubmit the remainder
        # in bounded retry rounds — a resubmission can itself be shed
        # (ladder still hot) or re-dropped by the injector, so each
        # round cools the ladder one recovery window and retries what
        # is still unconfirmed. Deterministic: every draw still comes
        # from the seeded plan in call order.
        srv.log.flush_delayed()
        srv.pump()
        for _ in range(20):
            vclock["t"] += 0.2
            adm.observe(force=True)
            unacked = {c: [u for u in pending[c]
                           if u not in seen_payloads]
                       for c in range(self.N_CLIENTS)}
            if not any(unacked.values()):
                break
            for c in range(self.N_CLIENTS):
                for u in unacked[c]:
                    submit(c, u)
            srv.log.flush_delayed()
            srv.pump()
        vclock["t"] += 1.0
        adm.observe(force=True)

        op_payloads = [k for k in seen_a]
        return {
            "fingerprint": plan.fingerprint(),
            "stream_a": seen_a,
            "stream_b": seen_b,
            "payloads": sorted(seen_payloads),
            "uid": uid,
            "peak_backlog": peak_backlog,
            "adm_state": adm.state,
            "ops": op_payloads,
        }

    def test_converges_and_reproduces_bit_identically(self):
        a = self._run(20260803)
        b = self._run(20260803)
        assert a["fingerprint"] == b["fingerprint"]
        assert a["stream_a"] == b["stream_a"]
        # Both observers agree on one total order.
        assert a["stream_a"] == a["stream_b"]
        # Convergence: every submitted payload sequenced, exactly once
        # (drops recovered by resubmission, dups deduped by deli).
        assert a["payloads"] == list(range(1, a["uid"] + 1))
        counts = {}
        for seq, cid, csn in a["stream_a"]:
            counts[(cid, csn)] = counts.get((cid, csn), 0) + 1
        assert all(v == 1 for v in counts.values())
        # Sequence numbers strictly increase (no forks, no reuse).
        seqs = [s for s, _, _ in a["stream_a"]]
        assert seqs == sorted(set(seqs))
        # Bounded queue + the ladder settled back to ACCEPT.
        assert a["peak_backlog"] <= 512
        assert a["adm_state"] == ADM_ACCEPT

    def test_different_seeds_diverge(self):
        a = self._run(1)
        b = self._run(2)
        assert a["fingerprint"] != b["fingerprint"]


class TestHotDocumentChaos:
    """Every client hammers ONE document in plan-sized bursts while the
    injector delays/dups deliveries and stalls the drain — the hot-
    partition storm the admission controller must absorb: backlog stays
    under the limit (shedding, not queueing), admitted ops sequence
    exactly once, and the run reproduces from its seed."""

    N_CLIENTS = 4
    ROUNDS = 30
    QUEUE_LIMIT = 96

    def _run(self, seed):
        plan = faultinject.FaultPlan(seed, dup=0.15, delay=0.15,
                                     stall=0.3, max_delay_sends=3)
        srv, adm, vclock = _chaos_server(plan,
                                         queue_limit=self.QUEUE_LIMIT)
        conns = [srv.connect("hot") for _ in range(self.N_CLIENTS)]
        srv.pump()
        sequenced = []
        admitted = set()
        conns[0].on("op", lambda m: m.type == MessageType.OPERATION
                    and sequenced.append(
                        (m.sequence_number, _stable_cid(m.client_id),
                         m.client_sequence_number)))
        csns = [0] * self.N_CLIENTS
        stalls = []
        peak_backlog = 0
        shed = [0]
        for c in conns:
            c.on("nack", lambda n: shed.__setitem__(0, shed[0] + 1))

        for _ in range(self.ROUNDS):
            vclock["t"] += 0.02
            for ci in range(self.N_CLIENTS):
                burst = 1 + plan.pick(8, site="burst")
                for _ in range(burst):
                    csns[ci] += 1
                    before = shed[0]
                    conns[ci].submit([DocumentMessage(
                        client_sequence_number=csns[ci],
                        reference_sequence_number=0,
                        type=MessageType.OPERATION,
                        contents={"c": ci, "n": csns[ci]})])
                    if shed[0] == before:
                        admitted.add((ci, csns[ci]))
            peak_backlog = max(peak_backlog, srv.raw_backlog())
            # Stalled drain: the slow-device failure mode — some rounds
            # barely pump, and the backlog must hit admission, not RAM.
            if faultinject.stall(plan, sleep=stalls.append) > 0:
                _partial_pump(srv, limit=2)
            else:
                _partial_pump(srv, limit=self.N_CLIENTS * 6)

        srv.log.flush_delayed()
        srv.pump()
        return {
            "fingerprint": plan.fingerprint(),
            "sequenced": sequenced,
            "admitted": admitted,
            "peak_backlog": peak_backlog,
            "shed": shed[0],
            "stalls": len(stalls),
        }

    def test_bounded_and_exactly_once_and_deterministic(self):
        a = self._run(424242)
        b = self._run(424242)
        assert a["fingerprint"] == b["fingerprint"]
        assert a["sequenced"] == b["sequenced"]
        assert a["shed"] == b["shed"]
        # The storm actually overloaded the door at least once...
        assert a["shed"] > 0
        # ...and the raw backlog never outgrew the admission limit.
        assert 0 < a["peak_backlog"] <= self.QUEUE_LIMIT
        # Every admitted (client, csn) sequenced exactly once — dup
        # deliveries deduped, delayed ones recovered at flush.
        got = {}
        client_ids = {}
        for seq, cid, csn in a["sequenced"]:
            got[(cid, csn)] = got.get((cid, csn), 0) + 1
        assert all(v == 1 for v in got.values())
        # Ops from all clients made it through the hot partition.
        assert len({cid for _, cid, _ in a["sequenced"]}) \
            == self.N_CLIENTS


class TestAsyncSummaryLockDiscipline:
    """Fixed-seed serving traffic with async summaries in flight while
    the sequencing thread keeps flushing — the exact overlap the
    MergeLaneStore summarize-guard discipline exists for. The store
    runs instrumented with the locksets fluidlint v3 STATICALLY
    inferred (testing/lockcheck.py static_guards), so the model and the
    code cannot drift apart: a new unguarded access to the blob cache /
    deferred-free state fails here even if its static finding was
    suppressed. Deterministic (fixed seed, joined workers) — tier-1,
    no SOAK gate."""

    def test_inferred_locksets_hold_under_async_summaries(self):
        from fluidframework_tpu.server.log import QueuedMessage
        from fluidframework_tpu.server.tpu_sequencer import (
            TpuSequencerLambda)
        from fluidframework_tpu.testing.lockcheck import (instrument,
                                                          static_guards)

        class _Ctx:
            def checkpoint(self, *_):
                pass

            def error(self, err, restart=False):
                raise err

        seq = TpuSequencerLambda(_Ctx(), emit=lambda d, m: None,
                                 nack=lambda d, c, n: None,
                                 client_timeout_s=0.0)
        guards = static_guards(type(seq.merge))
        # The statically inferred guard map must cover the summarize
        # epoch state — if the model stops seeing the discipline, this
        # assert (not just the runtime wrap) catches the drift.
        assert guards.get("_snap_cache") == "_guard_lock"
        assert guards.get("_extract_guards") == "_guard_lock"
        assert guards.get("last_summarized_gen") == "_guard_lock"
        guards["_deferred_frees"] = "_guard_lock"
        lockcheck = instrument(seq.merge, guards)
        rng = random.Random(909_090)

        def boxcar(doc, csn, txt=None):
            if csn == 0:
                msg = DocumentMessage(
                    client_sequence_number=0,
                    reference_sequence_number=-1,
                    type=MessageType.CLIENT_JOIN,
                    data=json.dumps({"clientId": f"c-{doc}"}))
            else:
                msg = DocumentMessage(
                    client_sequence_number=csn,
                    reference_sequence_number=-1,
                    type=MessageType.OPERATION,
                    contents={"type": "insert", "pos1": 0,
                              "seg": {"text": txt}, "channel": "t",
                              "store": "s"})
            return Boxcar(tenant_id="t", document_id=doc, client_id=None,
                          contents=[msg])

        docs = [f"d{i}" for i in range(4)]
        offset = 0
        workers = []
        done = []
        try:
            for doc in docs:
                seq.handler(QueuedMessage("rawdeltas", 0, offset, doc,
                                          boxcar(doc, 0)))
                offset += 1
            for wave in range(6):
                for k in range(12):
                    doc = rng.choice(docs)
                    seq.handler(QueuedMessage(
                        "rawdeltas", 0, offset, doc,
                        boxcar(doc, wave * 12 + k + 1,
                               chr(97 + (offset % 26)))))
                    offset += 1
                    if rng.random() < 0.4:
                        seq.flush()
                seq.flush()
                seq.drain()
                # Async summary dispatched, then MORE sequencing while
                # the worker assembles — the contended overlap.
                workers.append(seq.summarize_documents_async(
                    lambda out: done.append(len(out))))
            for th in workers:
                th.join(10)
            lockcheck.assert_clean()
        finally:
            lockcheck.uninstrument()
        assert len(done) == len(workers)


# ---------------------------------------------------------------------------
# Fixed-seed READ-PATH chaos (docs/read_path.md): reconnect-avalanche
# loads through the catch-up delta artifact, and hot-document fan-out
# through the sharded broadcaster with forced shedding + gap-fill
# recovery. Deterministic (FaultPlan drives every decision), tier-1.
# ---------------------------------------------------------------------------


def _read_chaos_fleet(server, doc_id="doc", seed_text="base"):
    from fluidframework_tpu.dds.sequence import SharedString
    from fluidframework_tpu.loader.container import Loader
    from fluidframework_tpu.loader.drivers.local import (
        LocalDocumentServiceFactory)

    loader = Loader(LocalDocumentServiceFactory(server))
    c = loader.create_detached(doc_id)
    ds = c.runtime.create_datastore("default")
    t = ds.create_channel("text", SharedString.TYPE)
    t.insert_text(0, seed_text)
    c.attach()
    return loader, c, t


class TestReconnectAvalancheReadChaos:
    """N reader containers on one document served via the catch-up
    artifact; every round the plan picks a burst of writer edits and a
    set of readers to drop, then the WHOLE dropped set reloads at once
    (the avalanche) against a freshly refreshed artifact. Convergence:
    every reader ends on the writer's text, the delta path actually
    carried the avalanche (adoptions counted), the refresh stayed
    batched (dispatches never scale with reader count), and two
    same-seed runs reproduce bit-identically."""

    N_READERS = 6
    ROUNDS = 8

    def _run(self, seed):
        from fluidframework_tpu.server.local_server import TpuLocalServer
        from fluidframework_tpu.telemetry import counters

        plan = faultinject.FaultPlan(seed)
        server = TpuLocalServer()
        loader, writer, text = _read_chaos_fleet(server)
        readers = {}
        for r in range(self.N_READERS):
            readers[r] = loader.resolve(
                "doc", client_details={"mode": "read"})
        trace = []
        adopted0 = counters.get("catchup.client.adopted")
        disp0 = counters.get("catchup.refresh_dispatches")
        for _round in range(self.ROUNDS):
            burst = 4 + plan.pick(24, site="burst")
            for i in range(burst):
                text.insert_text(plan.pick(text.get_length() + 1,
                                           site="pos"),
                                 f"r{_round}.{i} ")
            server.pump()
            st = server.refresh_catchup()
            dropped = [r for r in readers
                       if plan.pick(3, site="drop") == 0]
            for r in dropped:
                readers[r].close()
            server.pump()
            # The avalanche: every dropped reader reloads at once.
            for r in dropped:
                readers[r] = loader.resolve(
                    "doc", client_details={"mode": "read"})
            trace.append((burst, tuple(dropped), st["published"]))
        server.pump()
        texts = {r: c.runtime.get_datastore("default")
                 .get_channel("text").get_text()
                 for r, c in readers.items()}
        return {
            "fingerprint": plan.fingerprint(),
            "trace": trace,
            "final": text.get_text(),
            "texts": texts,
            "adoptions": counters.get("catchup.client.adopted") - adopted0,
            "dispatches": counters.get("catchup.refresh_dispatches")
            - disp0,
        }

    def test_converges_and_reproduces_bit_identically(self):
        a = self._run(20260804)
        b = self._run(20260804)
        assert a["fingerprint"] == b["fingerprint"]
        assert a["trace"] == b["trace"]
        assert a["final"] == b["final"]
        # Convergence: every reader (including every avalanche reload)
        # sees exactly the writer's document.
        assert all(t == a["final"] for t in a["texts"].values())
        # The avalanche actually rode the delta path...
        assert a["adoptions"] > 0 and a["adoptions"] == b["adoptions"]
        # ...and refresh work stayed O(dirty docs): bounded by rounds x
        # buckets, NOT by reader-loads (one doc, one bucket here — at
        # most one dispatch per round regardless of avalanche size).
        assert a["dispatches"] <= self.ROUNDS
        assert a["dispatches"] == b["dispatches"]

    def test_different_seeds_diverge(self):
        a = self._run(31)
        b = self._run(32)
        assert a["fingerprint"] != b["fingerprint"]


class TestHotDocumentReadChaos:
    """One hot document fanned out through the SHARDED broadcaster to a
    crowd of read-only containers, with plan-chosen rounds running
    against a deliberately blocked shard so the bounded queue must shed.
    Readers that missed shed broadcasts recover through DeltaManager gap
    detection (catch-up fetch against scriptorium) — the read path's own
    recovery contract — and everyone converges. Shedding is
    deterministic (the queue fills while the shard is parked), so two
    same-seed runs reproduce bit-identically."""

    N_READERS = 5
    ROUNDS = 6
    QUEUE_LIMIT = 8

    def _run(self, seed):
        import threading

        from fluidframework_tpu.server.local_server import TpuLocalServer

        class Cfg(dict):
            def get(self, k, d=None):
                return dict.get(self, k, d)

        plan = faultinject.FaultPlan(seed)
        server = TpuLocalServer(config=Cfg({
            "broadcaster.shards": 2,
            "broadcaster.queueLimit": self.QUEUE_LIMIT}))
        loader, writer, text = _read_chaos_fleet(server, doc_id="hot")
        server.pump()
        server.drain_broadcast(20.0)
        readers = [loader.resolve("hot", client_details={"mode": "read"})
                   for _ in range(self.N_READERS)]
        lam = server.broadcasters[0]
        from fluidframework_tpu.server.lambdas.broadcaster import shard_for
        hot_shard = lam.shards[shard_for("hot", len(lam.shards))]
        trace = []
        for _round in range(self.ROUNDS):
            burst = 6 + plan.pick(18, site="burst")
            stall = plan.pick(2, site="stall") == 0
            gate = threading.Event()
            if stall:
                # Park the hot shard: one in-flight delivery blocks on
                # the gate, the burst then overfills the bounded queue
                # and sheds deterministically.
                lam.join_room("hot", lambda m: gate.wait(30.0))
            shed0 = lam.shed_count()
            for i in range(burst):
                text.insert_text(text.get_length(), f"h{_round}.{i} ")
            server.pump()
            if stall:
                gate.set()
                lam.leave_room(
                    "hot", [l for l in lam.rooms["hot"]][-1])
            server.drain_broadcast(30.0)
            trace.append((burst, stall, lam.shed_count() - shed0))
            assert hot_shard.depth() <= self.QUEUE_LIMIT
        # Closing edit exposes any shed-induced gap; DeltaManager
        # gap-fill then recovers every reader.
        text.insert_text(text.get_length(), "END")
        server.pump()
        server.drain_broadcast(30.0)
        final = text.get_text()
        reader_texts = [c.runtime.get_datastore("default")
                        .get_channel("text").get_text()
                        for c in readers]
        return {
            "fingerprint": plan.fingerprint(),
            "trace": trace,
            "final": final,
            "reader_texts": reader_texts,
            "shed": lam.shed_count(),
        }

    def test_sheds_recovers_and_reproduces(self):
        a = self._run(777)
        b = self._run(777)
        assert a["fingerprint"] == b["fingerprint"]
        assert a["trace"] == b["trace"]
        assert a["final"] == b["final"]
        # The bounded queue actually shed under the parked shard...
        assert a["shed"] > 0 and a["shed"] == b["shed"]
        # ...and every reader still converged on the writer's document
        # (gap-fill recovery, not broadcast delivery, is the contract).
        assert all(t == a["final"] for t in a["reader_texts"])
