"""The main path's kernels compile for a TPU v5e chip, at real widths.

Nothing here runs on a chip: each test lowers a program for described
(not attached) v5e devices and asserts the TPU compiler accepts it — the
single-chip kernels with their Pallas body intact (a ``tpu_custom_call``
in the compiled text), the four-chip programs with their shardings. That
catches what interpret mode cannot — unaligned slices, VMEM over-use, a
Mosaic kernel GSPMD cannot partition — before any chip time is spent.
The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from fluidframework_tpu.mergetree import pallas_apply  # noqa: E402
from fluidframework_tpu.mergetree.constants import PAGE_ROWS  # noqa: E402
from fluidframework_tpu.mergetree.oppack import (  # noqa: E402
    RUN_K, PackedOps, RunCols)
from fluidframework_tpu.mergetree.pallas_ops import (  # noqa: E402
    _pallas_summary_lengths, _summary_cols)
from fluidframework_tpu.mergetree.state import make_state  # noqa: E402
from fluidframework_tpu.server import lww_kernel as lk  # noqa: E402
from fluidframework_tpu.server import serve_step  # noqa: E402
from fluidframework_tpu.server import ticket_kernel as tk  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on_mesh(mesh, tree, spec):
    from jax.sharding import NamedSharding

    return _shaped(tree, lambda x: NamedSharding(mesh, spec(x)))


def _spec(one_chip, tree):
    return _shaped(tree, lambda x: one_chip)


def _shaped(tree, sharding):
    """Shapes of ``tree()`` (never allocated) with a sharding each."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding(x)),
        jax.eval_shape(tree))


def _arr(one_chip, shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def _ops(one_chip, b, t):
    return PackedOps(**{f: _arr(one_chip, (b, t))
                        for f in PackedOps._fields})


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("variant", ["plain", "extract", "runs"])
def test_fused_apply_compiles(one_chip, variant):
    b, c, t = 4096, 256, 16
    state = _spec(one_chip, lambda: make_state(c, 1, batch=b))
    runs = None
    if variant == "runs":
        runs = RunCols(*(_arr(one_chip, (b, t, RUN_K)) for _ in range(3)))
    fn = jax.jit(lambda s, o, r: pallas_apply.apply_ops_fused_pallas(
        s, o, runs=r, extract=variant == "extract"))
    _assert_kernel(fn.lower(state, _ops(one_chip, b, t), runs))


def test_summary_lengths_compiles(one_chip):
    state = _spec(one_chip, lambda: make_state(256, 1, batch=10_000))
    fn = jax.jit(lambda s: _pallas_summary_lengths(*_summary_cols(s),
                                                   interpret=False))
    _assert_kernel(fn.lower(state))


@pytest.mark.parametrize("k", [1, 8])
def test_serving_megakernel_compiles(one_chip, k):
    """The paged serving megakernel, fused (the Pallas op phase), over a
    pool sized for 10,000 docs: a 16,384-lane group at one page per doc,
    a 2,048-lane group at four, and one LWW bucket."""
    lanes, t = 16_384, 16
    groups = [(16_384, 1), (2_048, 4)]
    tstate = _spec(one_chip, lambda: tk.make_ticket_state(8, batch=lanes))
    pool = _spec(one_chip, lambda: make_state(PAGE_ROWS,
                                              batch=lanes + 4_096))
    lww = (_spec(one_chip, lambda: lk.make_lww_state(64, batch=2_048)),)
    tables = tuple(_arr(one_chip, g) for g in groups)
    scalars = tuple(_arr(one_chip, (n,)) for n, _ in groups)
    merge_xs = tuple(_arr(one_chip, (k, 12, n, t)) for n, _ in groups)
    runs_xs = tuple(_arr(one_chip, (k, 4, n, t, RUN_K)) for n, _ in groups)
    lww_xs = (_arr(one_chip, (k, 6, 2_048, t)),)
    _assert_kernel(serve_step.serve_megakernel.lower(
        tstate, pool, lww, _arr(one_chip, (k, 4, lanes, t)), tables,
        scalars, scalars, scalars, merge_xs, lww_xs, runs_xs, True, True))


def test_mesh_serving_megakernel_compiles(topo):
    """The dp=4 serving path: the non-donating megakernel (scan op
    phase) with lanes, pages and op planes sharded over 'dp'."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("dp", "sp"))
    lanes, t, k = 16_384, 16, 1
    lead = lambda x: P("dp") if x.ndim else P()  # noqa: E731
    plane = lambda x: P(*[("dp" if i == 2 else None)  # noqa: E731
                          for i in range(x.ndim)])
    tstate = _on_mesh(mesh, lambda: tk.make_ticket_state(8, batch=lanes),
                      lead)
    pool = _on_mesh(mesh, lambda: make_state(PAGE_ROWS, batch=lanes),
                    lead)
    lww = (_on_mesh(mesh, lambda: lk.make_lww_state(64, batch=2_048),
                    lead),)
    table = _on_mesh(mesh, lambda: jnp.zeros((lanes, 1), jnp.int32),
                     lambda x: P())
    scalars = _on_mesh(mesh, lambda: jnp.zeros((lanes,), jnp.int32),
                       lambda x: P())

    def xs(*shape):
        return _on_mesh(mesh, lambda: jnp.zeros(shape, jnp.int32), plane)

    serve_step.serve_megakernel_keep.lower(
        tstate, pool, lww, xs(k, 4, lanes, t), (table,), (scalars,),
        (scalars,), (scalars,), (xs(k, 12, lanes, t),),
        (xs(k, 6, 2_048, t),), (xs(k, 4, lanes, t, RUN_K),), False,
        True).compile()


def test_dp_sp_full_step_compiles(topo, monkeypatch):
    """full_step on dp=2 x sp=2 as the chip traces it: the Pallas summary
    runs per shard under shard_map, since GSPMD cannot partition a Mosaic
    kernel."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from fluidframework_tpu.server.pipeline import make_full_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2), ("dp", "sp"))
    docs, n_ops, cap = 10_000, 100, 256
    lead = lambda x: P("dp") if x.ndim else P()  # noqa: E731
    seq = lambda x: P(*(["dp", "sp"] + [None] * (x.ndim - 2))  # noqa: E731
                      if x.ndim >= 2 else lead(x))
    tstate = _on_mesh(mesh, lambda: tk.make_ticket_state(8, batch=docs),
                      lead)
    mstate = _on_mesh(mesh, lambda: make_state(cap, 1, batch=docs), seq)
    cols = lambda: jnp.zeros((docs, n_ops), jnp.int32)  # noqa: E731
    raw = tk.RawOps(*(_on_mesh(mesh, cols, lead) for _ in range(3)))
    ops = PackedOps(**{f: _on_mesh(mesh, cols, lead)
                       for f in PackedOps._fields})
    _assert_kernel(jax.jit(make_full_step(sp_shards=2, fused_apply=True,
                                          mesh=mesh)).lower(
        tstate, mstate, raw, ops))
