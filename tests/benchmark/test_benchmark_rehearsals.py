"""CPU rehearsals of the drivers whose cells are not in the manifest yet (the
host-env trainer, the gateway under a closed loop), so that they cannot rot
unseen, and the precision guard of the correctness check shown to bite.
Tier-1; no chip, no speed. In a file of its own so that it runs beside
test_benchmark_harness.py, not after it."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def _rehearse(cell: str, trace: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TPU_LOG_DIR": "disabled"}
    try:
        r = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "3",
             "--seconds", "1", "--trace", str(trace), "--rehearsal"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    finally:
        shutil.rmtree(os.path.join(ROOT, ".bench_scratch", cell), ignore_errors=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert not any(ln.startswith("{\"correct") for ln in r.stdout.splitlines())
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("REHEARSAL")]
    would = json.loads(lines[-1].split("would print: ", 1)[1])
    assert set(would) == RESULT_KEYS
    assert would["correct"] is True and would["failed"] == 0
    assert would["attempted"] > 0
    # Each number that decided `correct` beside its limit, last in the line.
    assert list(would)[-1] == "compared" and len(would["compared"]) >= 4
    assert all(0 <= number <= limit for number, limit in would["compared"].values())
    return would["metrics"]


def test_rehearsal_of_the_host_env_trainer():
    """`ppo_halfcheetah.train` through train.main on the host path: the
    spans its readers need are there and give shares and durations."""
    m = _rehearse("ppo_halfcheetah.train", 1)
    assert 0.0 < m["env_step_pct"]["value"] < 100.0
    assert m["update_span_ms"]["value"] > 0 and m["h2d_ms"]["value"] > 0
    assert m["compiles_in_window"] == {"value": 0.0, "unit": "count"}
    assert "setup_s" not in m and "host_steps_per_s" not in m


def test_rehearsal_of_the_gateway_under_a_closed_loop():
    """`ppo_halfcheetah.serve_actors`: scripts/serve.py's main in this
    process, the load generator in a child, the check before and after."""
    m = _rehearse("ppo_halfcheetah.serve_actors", 0)
    assert set(m) == {"act_per_s", "act_p99_ms", "setup_s"}
    assert m["act_per_s"]["value"] > 0 and m["act_p99_ms"]["value"] > 0
    assert m["act_per_s"]["unit"] == "actions/s"


# -- the precision the configuration states --------------------------------

def test_narrow_matmuls_on_hand_made_programs():
    import jax
    import jax.numpy as jnp

    x, w = jnp.ones((4, 8)), jnp.ones((8, 8))

    def plain(x, w):
        return jnp.sum(jnp.tanh(x @ w) @ w)

    def narrow_inside_a_scan(x, w):
        def body(c, _):
            y = jnp.dot(c.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
            return y, ()
        return jnp.sum(jax.lax.scan(body, x, None, length=3)[0])

    trace = lambda f: jax.make_jaxpr(jax.grad(f))(x, w)  # noqa: E731
    assert harness.narrow_matmuls(trace(plain), "float32") == []
    found = harness.narrow_matmuls(trace(narrow_inside_a_scan), "float32")
    # Forward and both transposes, found inside the scan's body; a float32
    # result does not hide bf16 operands.
    assert found and set(found) == {"dot_general:bfloat16"}
    assert harness.narrow_matmuls(trace(narrow_inside_a_scan), "bfloat16") == []


@pytest.mark.parametrize("update_dtype, narrow", [
    ("fp32", []),
    ("bf16", ["conv_general_dilated:bfloat16", "dot_general:bfloat16"]),
])
def test_check_fails_a_bf16_update_in_a_float32_configuration(update_dtype, narrow):
    """The driver's own check on `impala_pong.fleet` at rehearsal shapes: as
    shipped it passes; with `--update-dtype bf16` the traced step program
    holds bf16 convolutions and `correct` is false whatever the tolerance."""
    load = lambda kind, name: harness.load_json(kind, f"{name}.json")  # noqa: E731
    workload = load("workloads", "impala_pong.fleet")
    traffic = load("traffic", workload["traffic"])
    traffic["flags"] = traffic["flags"] + ["--update-dtype", update_dtype]
    config = load("configs", workload["config"])
    config["tolerance"]["cpu"] = {"adv_tol": 1.0, "loss_tol": 1.0}
    ctx = harness.Ctx(workload, config, traffic, 3, 1.0, False, True, "")
    verdict = harness.load_module("drivers", workload["driver"]).check(ctx)
    assert verdict["narrow"] == narrow
    assert verdict["ok"] is (not narrow)
