"""chip_smoke.py off the chip: its numpy references agree with the
driver and the tenant cohort at a tiny size on the CPU (the same
checks the chip run makes at full size), its checks catch a wrong
answer, and the script itself refuses to pass anywhere but a TPU."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from bench import make_stream  # noqa: E402

EB, VB = 256, 512


@pytest.fixture(scope="module")
def stream():
    src, dst = make_stream(16 * EB, VB, seed=7)
    return src, dst, chip_smoke.ref_driver_windows(src, dst, EB, VB)


@pytest.fixture
def guards(monkeypatch):
    monkeypatch.setenv("GS_TIER_DEMOTE", "0")
    from gelly_streaming_tpu.utils import resilience

    resilience.reset_demotions()
    return chip_smoke._Guards()


@pytest.mark.parametrize("tier", ["scan", "sharded"])
def test_driver_phase_matches_reference(stream, guards, monkeypatch, tier):
    """Both tiers the chip runs: the single-chip device scan and the
    4-device mesh (virtual CPU devices here)."""
    from gelly_streaming_tpu.core import driver
    from gelly_streaming_tpu.parallel.mesh import make_mesh

    src, dst, refs = stream
    mesh = None
    if tier == "scan":
        monkeypatch.delenv("GS_RESIDENT", raising=False)
        assert driver.resolve_snapshot_tier() == "scan"
    else:
        mesh = make_mesh(4)
    chip_smoke.run_driver(src, dst, refs, guards, EB, VB, mesh=mesh)
    guards.check()


def test_serving_phase_matches_reference(guards):
    chip_smoke.run_serving(7, windows=4, eb=EB, vb=VB)
    guards.check()


def test_reference_checks_catch_a_wrong_answer(stream):
    """The driver check is not vacuous: flipped odd flags, split
    components or one triangle too many each fail it."""
    from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver

    src, dst, refs = stream
    got = StreamingAnalyticsDriver(window_ms=0, vertex_bucket=VB,
                                   edge_bucket=EB).run_arrays(src, dst)
    chip_smoke.check_driver_results(got, refs, "ok")
    last = got[-1]
    for field, bad in (("bipartite_odd", lambda a: ~a.astype(bool)),
                       ("cc_labels", lambda a: np.arange(len(a))),
                       ("triangles", lambda t: t + 1)):
        val = getattr(last, field)
        setattr(last, field, bad(val))
        with pytest.raises(AssertionError, match="window 15"):
            chip_smoke.check_driver_results(got, refs, "bad")
        setattr(last, field, val)


def test_ref_triangles_brute_force():
    rng = np.random.default_rng(3)
    s = rng.integers(0, 12, 60)
    d = rng.integers(0, 12, 60)
    adj = np.zeros((12, 12), bool)
    adj[s, d] = adj[d, s] = True
    np.fill_diagonal(adj, False)
    a = adj.astype(np.int64)
    assert chip_smoke.ref_triangles(s, d) == np.trace(a @ a @ a) // 6


def _run_smoke(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


def test_smoke_refuses_without_a_tpu():
    r = _run_smoke(REPO, "chip_smoke.py")
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no TPU (JAX found 'cpu')" in r.stderr


def test_smoke_refuses_outside_the_repo(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_smoke(str(tmp_path), "chip_smoke.py")
    assert r.returncode != 0
    assert r.stdout == ""
