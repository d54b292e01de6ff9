"""Where the program finds its backend, its compile cache and its native
libraries: no silent CPU fallback, a cache placed from outside (or at a
fixed path in the checkout), and libraries keyed on their source."""

import os
import sys

import pytest

from fluidframework_tpu.core import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    import jax

    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert platform.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert platform.enable_compile_cache() == \
            os.path.join(REPO, ".jax_cache")
    finally:
        monkeypatch.undo()
        platform.enable_compile_cache()


def test_repo_cache_dir_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("platforms,ok", [
    ("cpu", True),
    ("", False),
    (None, False),
])
def test_bench_refuses_the_cpu_unless_asked(monkeypatch, platforms, ok):
    sys.path.insert(0, REPO)
    import bench

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    if platforms is not None:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if ok:
        bench._require_backend()
    else:
        with pytest.raises(SystemExit, match="no TPU"):
            bench._require_backend()


@pytest.fixture
def native_tree(monkeypatch, tmp_path):
    from fluidframework_tpu.native import build

    src, lib = tmp_path / "src", tmp_path / "lib"
    src.mkdir()
    monkeypatch.setattr(build, "SRC_DIR", str(src))
    monkeypatch.setattr(build, "LIB_DIR", str(lib))
    (src / "tiny.cpp").write_text('extern "C" int tiny() { return 1; }\n')
    return build, src / "tiny.cpp"


def test_native_library_is_keyed_on_its_source(native_tree):
    build, src = native_tree
    first = build.ensure_built("tiny")
    assert os.path.basename(first).startswith("tiny-")
    assert build.ensure_built("tiny") == first  # no rebuild
    src.write_text('extern "C" int tiny() { return 2; }\n')
    second = build.lib_path("tiny")
    assert second != first and not os.path.exists(second)
    assert build.ensure_built("tiny") == second


def test_a_newer_library_of_other_source_is_never_loaded(native_tree):
    build, src = native_tree
    stale = build.ensure_built("tiny")
    os.utime(stale, (4e9, 4e9))  # newer than any source
    src.write_text('extern "C" int tiny() { return 3; }\n')
    assert build.ensure_built("tiny") != stale


def test_a_rebuild_leaves_one_library_per_source(native_tree):
    build, src = native_tree
    (src.parent / "tinyx.cpp").write_text('extern "C" int x() { return 0; }\n')
    other = build.ensure_built("tinyx")
    first = build.ensure_built("tiny")
    src.write_text('extern "C" int tiny() { return 4; }\n')
    second = build.ensure_built("tiny")
    assert not os.path.exists(first)
    assert sorted(os.listdir(os.path.dirname(second))) == sorted(
        os.path.basename(p) for p in (other, second))


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py",
                                    "__graft_entry__.py"])
def test_entry_scripts_import_only_what_exists(script):
    """Every `from fluidframework_tpu... import x` in an entry script,
    at any depth of any function, names something the package has: a
    removed helper fails here, not on the chip."""
    import ast
    import importlib

    with open(os.path.join(REPO, script)) as f:
        tree = ast.parse(f.read())
    checked = 0
    for node in ast.walk(tree):
        if (isinstance(node, ast.ImportFrom) and node.level == 0
                and node.module.startswith("fluidframework_tpu")):
            mod = importlib.import_module(node.module)
            for alias in node.names:
                assert hasattr(mod, alias.name) or importlib.util.find_spec(
                    f"{node.module}.{alias.name}"), \
                    f"{script}: {node.module} has no {alias.name}"
                checked += 1
    assert checked


def test_bench_headline_step_builds_and_runs_on_cpu(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from fluidframework_tpu.mergetree.oppack import PackedOps
    from fluidframework_tpu.mergetree.state import make_state
    from fluidframework_tpu.server import ticket_kernel as tk
    from fluidframework_tpu.testing.traces import gen_traces

    sys.path.insert(0, REPO)
    import bench

    monkeypatch.delenv("BENCH_FUSED", raising=False)
    fused, step = bench.headline_step()
    assert fused is False  # the fused Pallas apply is the TPU's
    cols = gen_traces(4, 8, seed=0)
    ops = PackedOps(**{f: jnp.asarray(cols[f]) for f in PackedOps._fields})
    raw = tk.RawOps(client=ops.client, client_seq=ops.seq,
                    ref_seq=ops.ref_seq)
    _, mstate, ticketed, total_len = step(
        tk.make_ticket_state(8, batch=4), make_state(32, 1, batch=4), raw,
        ops)
    assert np.asarray(total_len).shape == (4,)
    assert (np.asarray(ticketed.seq)[:, -1] > 0).all()
    assert not np.asarray(mstate.overflow).any()
