"""chip_smoke.py at tiny sizes on the CPU: every phase's checks run and
pass here (the Pallas kernels in interpret mode, the mesh on virtual
devices), and the script refuses to report a result without a TPU."""

import functools
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture
def interpret_pallas(monkeypatch):
    from fluidframework_tpu.mergetree import pallas_apply

    monkeypatch.setattr(
        pallas_apply, "apply_ops_fused_pallas",
        functools.partial(pallas_apply.apply_ops_fused_pallas,
                          interpret=True))


def test_kernel_phase_matches_oracle(interpret_pallas):
    out = chip_smoke.kernel_phase(docs=16, n_ops=20, capacity=64,
                                  sample=16)
    assert out["docs_checked"] == 16


def test_served_phase_matches_scalar_deli_and_replicas():
    out = chip_smoke.served_phase(docs=24, expect_fused=False)
    assert out["docs_checked"] == 24 and out["megakernel_rings"] > 0
    assert not any(out["faults"].values())


def test_served_traffic_carries_every_channel():
    waves = chip_smoke.served_traffic(docs=9)
    _, sequenced = chip_smoke.scalar_deli(waves)
    text, grid, tree = chip_smoke.oracle_channels(sequenced["doc8"])
    assert text and tree["subdirectories"]
    rows, cols = chip_smoke.MATRIX_SHAPE
    assert len(grid) >= rows - chip_smoke.WAVES * chip_smoke.RICH_OPS
    assert any(v is not None for row in grid for v in row)
    assert chip_smoke.oracle_channels(sequenced["doc1"])[1] == []


def test_loader_phase_summarizes_and_bulk_catches_up():
    out = chip_smoke.loader_phase(min_segments=120, tail_ops=200,
                                  dispatch_reps=1)
    assert out["bulk_catchup_count"] >= 1
    assert out["live_segments_at_summary"] >= 120


def test_mesh_phases_match_one_device(interpret_pallas):
    served = chip_smoke.mesh_served_phase(docs=16, dp=4)
    assert served["planes_checked"] > 0 and served["shardcheck_leaves"]
    chip_smoke.mesh_kernel_phase(docs=16, n_ops=20, capacity=64)


def _run_alone(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_fails_without_a_result_line():
    proc = _run_alone(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok": true' not in proc.stdout


def test_script_alone_fails_without_a_result_line(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_alone(str(tmp_path), "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    for line in proc.stdout.splitlines():
        assert json.loads(line).get("ok") is not True
