"""chip_smoke.py's contract as far as a CPU can check it, and the
multi-device body it shares with the driver's dry run
(`__graft_entry__.multichip_steps`) — so `dryrun_multichip(n)` and the
smoke's dp leg cannot drift apart."""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _python(code_or_script: list[str]) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, *code_or_script], cwd=REPO, capture_output=True,
        text=True, timeout=180, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_bare_run_without_a_chip_fails_and_says_why():
    r = _python(["chip_smoke.py"])
    assert r.returncode == 2, (r.stdout, r.stderr)
    assert "no TPU" in r.stderr
    assert '"ok"' not in r.stdout, "no result line without a chip"


_INJECT = """
import chip_smoke
ran = []
def boom(smoke):
    raise RuntimeError("injected leg failure")
legs = [("first", lambda s: ran.append("first")), ("boom", boom),
        ("never", lambda s: print("the leg after the failure ran"))]
raise SystemExit(chip_smoke.main(["--rehearsal"], legs=legs[:{n}]))
"""


def test_a_raising_leg_cannot_end_in_exit_0():
    """No leg can fail while the process exits 0: the exception ends the
    run (non-zero, traceback) before any later leg or any result line."""
    r = _python(["-c", _INJECT.format(n=3)])
    assert r.returncode != 0
    assert "injected leg failure" in r.stderr
    assert "the leg after the failure ran" not in r.stdout
    assert '"ok"' not in r.stdout and "passed" not in r.stdout


def test_rehearsal_tags_every_line_and_prints_no_result():
    r = _python(["-c", _INJECT.format(n=1)])
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines and all(ln.startswith("REHEARSAL platform=cpu") for ln in lines), lines
    assert '"ok"' not in r.stdout


def test_multichip_steps_places_shards_on_four_fake_devices():
    """The same steps and the same placement assertions the chip's dp leg
    makes, on 4 of conftest's fake CPU devices."""
    import __graft_entry__ as graft

    devices = jax.devices()[:4]
    out = graft.multichip_steps(devices, a2c_num_envs=32)
    assert out["sp"] == 2 and out["dp"] == 2
    state = out["a2c_state"]
    graft.check_dp_placement(state, devices, 32)  # 8 rows on each of 4
    # The assertions bite: a wrong row count or a wrong device set fails.
    with pytest.raises(AssertionError):
        graft.check_dp_placement(state, devices, 64)
    with pytest.raises(AssertionError):
        graft.check_dp_placement(state, jax.devices()[:2], 32)
    replicated = state._replace(rollout=state.params)
    with pytest.raises(AssertionError):
        graft.check_dp_placement(replicated, devices, 32)
