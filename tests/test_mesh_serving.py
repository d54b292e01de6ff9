"""Multi-chip serving: the TPU sequencer lambda on a dp mesh.

Ticket lanes and merge/LWW channel lanes shard over 'dp' (lanes are
embarrassingly parallel); the fused serving window compiles and executes
under GSPMD on the conftest's 8 virtual CPU devices. Reference analog:
one deli consumer per kafka partition scaling horizontally
(partitionManager.ts:22), collapsed onto one device mesh."""

import json

import jax
import numpy as np
import pytest

from fluidframework_tpu.dds.counter import SharedCounter
from fluidframework_tpu.dds.map import SharedMap
from fluidframework_tpu.dds.sequence import SharedString
from fluidframework_tpu.loader.container import Loader
from fluidframework_tpu.loader.drivers.local import LocalDocumentServiceFactory
from fluidframework_tpu.parallel.mesh import make_mesh
from fluidframework_tpu.server.local_server import TpuLocalServer

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs the 8-virtual-device mesh")


def make_doc(server, doc_id="doc"):
    loader = Loader(LocalDocumentServiceFactory(server))
    c = loader.create_detached(doc_id)
    ds = c.runtime.create_datastore("default")
    return loader, c, ds


class TestMeshServing:
    def test_multi_client_convergence_on_sharded_sequencer(self):
        mesh = make_mesh(sp=1)  # dp = all 8 devices
        server = TpuLocalServer(mesh=mesh)
        docs = {}
        loaders = {}
        for d in range(12):  # more docs than dp shards
            loader, c, ds = make_doc(server, f"m{d}")
            t = ds.create_channel("text", SharedString.TYPE)
            m = ds.create_channel("meta", SharedMap.TYPE)
            c.attach()
            t.insert_text(0, f"doc{d}:")
            m.set("d", d)
            docs[f"m{d}"] = (c, t, m)
            loaders[f"m{d}"] = loader
        # Second clients edit concurrently.
        for d in range(12):
            c2 = loaders[f"m{d}"].resolve(f"m{d}")
            t2 = c2.runtime.get_datastore("default").get_channel("text")
            t2.insert_text(t2.get_length(), f"+peer{d}")
            docs[f"m{d}"] += (c2, t2)
        for d in range(12):
            c, t, m, c2, t2 = docs[f"m{d}"]
            assert t.get_text() == t2.get_text()
            assert server.sequencer().channel_text(
                f"m{d}", "default", "text") == t.get_text()
        # The ticket state REALLY spans the mesh.
        lam = server.sequencer()
        assert len(lam.tstate.next_seq.sharding.device_set) == 8
        assert ("m0", "default", "text") in lam.merge.where
        pool = lam.merge.pages.pool
        assert len(pool.length.sharding.device_set) == 8

    def test_mesh_fast_path_matches_unsharded(self):
        """Identical wire-bytes traffic through a mesh lambda and an
        unsharded lambda: same emits, same materialization."""
        from fluidframework_tpu.protocol.messages import (
            Boxcar,
            DocumentMessage,
            MessageType,
        )
        from fluidframework_tpu.server import pump as pump_mod
        from fluidframework_tpu.server.log import QueuedMessage
        from fluidframework_tpu.server.tpu_sequencer import (
            TpuSequencerLambda,
        )
        from fluidframework_tpu.server.wire import boxcar_to_wire
        if not pump_mod.available():
            pytest.skip("native wirepump unavailable")

        class _Ctx:
            def checkpoint(self, *_):
                pass

            def error(self, err, restart=False):
                raise err

        def traffic():
            out = []
            for d in range(10):
                doc = f"w{d}"
                msgs = [DocumentMessage(0, -1, MessageType.CLIENT_JOIN,
                                        data=json.dumps(
                                            {"clientId": f"c{d}",
                                             "detail": {}}))]
                for i in range(6):
                    msgs.append(DocumentMessage(
                        i + 1, i, MessageType.OPERATION,
                        contents={"address": "s", "contents": {
                            "address": "t", "contents": {
                                "type": 0, "pos1": 0,
                                "seg": {"text": f"{d}:{i} "}}}}))
                out.append(QueuedMessage(
                    "rawdeltas", 0, d, doc,
                    boxcar_to_wire(Boxcar("t", doc, f"c{d}", msgs))))
            return out

        def run(mesh):
            emits = []
            lam = TpuSequencerLambda(
                _Ctx(), emit=lambda doc, m: emits.append(
                    (doc, m.sequence_number, m.minimum_sequence_number,
                     m.type)),
                nack=lambda *a: None, client_timeout_s=0.0, mesh=mesh)
            for qm in traffic():
                lam.handler_raw(qm)
            lam.flush()
            texts = {d: lam.channel_text(f"w{d}", "s", "t")
                     for d in range(10)}
            return sorted(emits), texts

        ea, ta = run(None)
        eb, tb = run(make_mesh(sp=1))
        assert ea == eb
        assert ta == tb

    def test_restart_rebuild_on_mesh(self):
        mesh = make_mesh(sp=1)
        server = TpuLocalServer(mesh=mesh)
        loader, c, ds = make_doc(server, "mr")
        t = ds.create_channel("text", SharedString.TYPE)
        k = ds.create_channel("n", SharedCounter.TYPE)
        c.attach()
        t.insert_text(0, "before ")
        k.increment(4)
        server._deli_mgr.restart()
        t.insert_text(7, "after")
        k.increment(1)
        assert server.sequencer().channel_text(
            "mr", "default", "text") == "before after"
        snap = server.sequencer().channel_snapshot("mr", "default", "n")
        assert snap["counter"] == 5
        assert len(server.sequencer().tstate.next_seq
                   .sharding.device_set) == 8

    def test_paged_lanes_on_mesh_place_via_partition_rules(self):
        """The pool-partition takeover (was: NotImplementedError
        refusal): a paged sequencer CONSTRUCTS on a dp mesh — the page
        pool placed leaf-by-leaf via
        mergetree/partition_rules.POOL_PARTITION_RULES — serves real
        traffic with donation gated off (R6: donated dp-sharded planes
        corrupt on warm reload through the persistent compile cache),
        and the runtime shardcheck proves every device-resident plane
        sits exactly where the rule table predicts."""
        from fluidframework_tpu.testing import shardcheck
        mesh = make_mesh(sp=1)
        server = TpuLocalServer(mesh=mesh)
        loader, c, ds = make_doc(server, "pgm")
        t = ds.create_channel("text", SharedString.TYPE)
        c.attach()
        t.insert_text(0, "paged ")
        t.insert_text(6, "mesh")
        lam = server.sequencer()
        assert lam.channel_text("pgm", "default", "text") == "paged mesh"
        # R6: mesh construction selects the non-donating dispatches.
        assert lam.merge.pages.mesh is mesh
        assert lam.merge.pages.donate is False
        # The pool really spans the mesh, exactly as the table says.
        checked = shardcheck.verify_store(lam.merge, mesh)
        assert checked > 0
        specs = lam.merge.pages.placement_spec_table()
        assert specs["length"] == "PartitionSpec('dp',)"

    def test_materialized_not_stale_after_sequencer_restart(self):
        """A crash-restart replaces the lambda (generation counters reset
        to 0); the materialized writer must not compare new counters to
        the old instance's watermarks and skip real edits."""
        server = TpuLocalServer(mesh=make_mesh(sp=1))
        loader, c, ds = make_doc(server, "rs")
        t = ds.create_channel("text", SharedString.TYPE)
        c.attach()
        t.insert_text(0, "one ")
        server.write_materialized_snapshots()
        server._deli_mgr.restart()  # fresh lambda, counters reset
        t.insert_text(4, "two")
        shas = server.write_materialized_snapshots()
        store = server.historian.store(server.tenant_id, "rs")
        tree = store.read_summary(shas["rs"])
        body = json.loads(tree.entries["default"].entries["text"]
                          .entries["chunk_0"].content)
        joined = "".join(e.get("text") or "" for e in body
                         if e.get("removedSeq") is None)
        assert joined == "one two", joined

    def test_mesh_larger_than_default_lanes(self):
        """dp > the default 8 LWW bucket lanes must grow-then-shard, and
        the page pool must round to a dp multiple, not crash (16-chip pod
        shape). Runs in a subprocess with 16 virtual devices."""
        import subprocess
        import sys
        code = (
            "from fluidframework_tpu.core.platform import "
            "force_host_platform\n"
            "force_host_platform(16)\n"
            "from fluidframework_tpu.parallel.mesh import make_mesh\n"
            "from fluidframework_tpu.server.tpu_sequencer import "
            "TpuSequencerLambda\n"
            "class C:\n"
            "    def checkpoint(self, *_): pass\n"
            "    def error(self, e, restart=False): raise e\n"
            "lam = TpuSequencerLambda(C(), emit=lambda *a: None, "
            "nack=lambda *a: None, mesh=make_mesh(sp=1))\n"
            "assert lam.lanes % 16 == 0\n"
            "for b in lam.lww.buckets:\n"
            "    assert b.lanes % 16 == 0, b.lanes\n"
            "assert lam.merge.pages.allocator.capacity % 16 == 0\n"
            "print('dp16 ok')\n")
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=300,
                             cwd="/root/repo")
        assert out.returncode == 0, out.stderr[-2000:]
        assert "dp16 ok" in out.stdout

    def test_materialized_snapshots_on_mesh(self):
        mesh = make_mesh(sp=1)
        server = TpuLocalServer(mesh=mesh)
        loader, c, ds = make_doc(server, "ms")
        t = ds.create_channel("text", SharedString.TYPE)
        c.attach()
        t.insert_text(0, "sharded extraction " * 5)
        shas = server.write_materialized_snapshots()
        store = server.historian.store(server.tenant_id, "ms")
        tree = store.read_summary(shas["ms"])
        body = json.loads(tree.entries["default"].entries["text"]
                          .entries["chunk_0"].content)
        joined = "".join(e.get("text") or "" for e in body
                         if e.get("removedSeq") is None)
        assert joined == t.get_text()

    def test_payload_collection_preserves_sharding(self):
        """Major collection (compact_payload_ids) rebuilds the origin_op/
        anno planes from host-built arrays; on a dp mesh the pool must be
        re-placed by its partition rules so the renumbered state keeps
        its sharding — and the renumbered ids must still resolve the
        exact text."""
        mesh = make_mesh(sp=1)
        server = TpuLocalServer(mesh=mesh)
        loader, c, ds = make_doc(server, "mc")
        t = ds.create_channel("text", SharedString.TYPE)
        c.attach()
        for i in range(60):
            t.insert_text(t.get_length(), f"w{i} ")
        store = server.sequencer().merge
        assert ("mc", "default", "text") in store.where
        pool = store.pages.pool
        assert len(pool.origin_op.sharding.device_set) == 8
        store.payload_compact_min_entries = 0
        assert store.compact_payload_ids() is True
        # The collection REALLY renumbered on sharded state and the
        # planes still span the mesh.
        assert store.payload_compactions >= 1
        pool = store.pages.pool
        assert len(pool.origin_op.sharding.device_set) == 8
        assert len(pool.anno.sharding.device_set) == 8
        # Renumbered ids resolve: materialization and further edits work.
        assert server.sequencer().channel_text(
            "mc", "default", "text") == t.get_text()
        t.insert_text(0, ">>")
        assert server.sequencer().channel_text(
            "mc", "default", "text") == t.get_text()

    def test_lww_value_compaction_preserves_sharding(self):
        """compact_values (the LWW major collection) renumbers the val
        plane from a host-built array — it must re-place on a dp mesh,
        same rule as the merge side's compact_payload_ids."""
        mesh = make_mesh(sp=1)
        server = TpuLocalServer(mesh=mesh)
        loader, c, ds = make_doc(server, "mv")
        m = ds.create_channel("meta", SharedMap.TYPE)
        c.attach()
        for i in range(30):
            m.set("k", f"v{i}")  # 29 superseded values to reclaim
        lww = server.sequencer().lww
        b, lane = lww.where[("mv", "default", "meta")]
        assert len(lww.buckets[b].state.val.sharding.device_set) == 8
        lww.compact_values()
        assert len(lww.buckets[b].state.val.sharding.device_set) == 8
        snap = server.sequencer().channel_snapshot("mv", "default", "meta")
        assert snap["entries"]["k"] == "v29"
        m.set("k2", "post")  # lanes still editable after re-place
        assert server.sequencer().channel_snapshot(
            "mv", "default", "meta")["entries"]["k2"] == "post"

    def test_host_fold_on_sharded_lanes(self):
        """The host rescue must work when the page pool is sharded over
        the dp mesh: the rollback scatter, the host fold of the gathered
        row, and the reseeding put_row all cross the sharding boundary.
        Sustained typing grows the lane page by page; one window of
        annotates past the ring's depth then forces the rescue, with
        exact text after."""
        import random

        mesh = make_mesh(sp=1)
        server = TpuLocalServer(mesh=mesh)
        loader, c, ds = make_doc(server, "mf")
        t = ds.create_channel("text", SharedString.TYPE)
        c.attach()
        store = server.sequencer().merge
        rng = random.Random(53)
        for i in range(400):
            pos = rng.randrange(t.get_length() + 1)
            t.insert_text(pos, f"s{i % 10}")
        key = ("mf", "default", "text")
        pages_grown = len(store.pages.tables[key])
        assert pages_grown > 1
        server.auto_pump = False
        for j in range(6):
            t.annotate_range(0, 5, {f"r{j}": j})
        server.auto_pump = True
        server.pump()
        assert store.paged_rescues > 0, "rescue never fired on the mesh"
        # The fold coalesced the acked typing into fewer pages.
        assert len(store.pages.tables[key]) < pages_grown
        # The rescued lane's pool REALLY spans the mesh (else this test
        # passes without crossing any sharding boundary).
        assert len(store.pages.pool.length.sharding.device_set) == 8
        assert server.sequencer().channel_text(
            "mf", "default", "text") == t.get_text()
        # Editing (incl. removes: position resolution against packed
        # tombstones) continues exactly against the folded sharded lanes.
        for i in range(40):
            if t.get_length() > 10 and rng.random() < 0.4:
                start = rng.randrange(t.get_length() - 4)
                t.remove_text(start, start + 3)
            else:
                t.insert_text(rng.randrange(t.get_length() + 1), "Q")
        assert server.sequencer().channel_text(
            "mf", "default", "text") == t.get_text()
