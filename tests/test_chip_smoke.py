"""``chip_smoke.py`` on the CPU: it must refuse to run without a TPU, and
its phases must pass when driven at a tiny size on the host backend."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_exits_nonzero_without_a_tpu():
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.fixture(scope="module")
def smoke():
    pytest.importorskip("jax")
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    return chip_smoke


def test_small_amg_phase_passes_on_every_device_backend(smoke):
    check = smoke.Check()
    smoke.amg_phase(check, smoke.CompileClock(), grid=8, torus=(1, 1, 1))
    assert check.failures == []


def test_a_real_winner_change_fails_the_comparison(smoke):
    from repro.comm.strategies import best_strategy_many
    _, phases = smoke.amg_phases(grid=8, torus=(1, 1, 1))
    want = best_strategy_many(phases, seed=0, backend="numpy")
    v = want[0]
    loser = max(v.model, key=v.model.get)
    assert v.model[loser] > v.model[v.model_winner] * (1 + smoke.RTOL)
    check = smoke.Check()
    ties = smoke.same_winner(check, loser, v.model_winner, v.model, "t")
    assert ties == 0 and len(check.failures) == 1
