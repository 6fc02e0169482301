"""Stall watchdog (utils/watchdog.py, SURVEY.md §5.3 failure detection).

The firing path calls os._exit, so it must be exercised in a subprocess;
the keep-alive path runs in-process.
"""

import os
import subprocess
import sys
import time

from actor_critic_tpu.utils import watchdog


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60, env=env,
    )


def test_fires_exit_42_on_stall():
    proc = _run(
        "import time\n"
        "from actor_critic_tpu.utils.watchdog import StallWatchdog\n"
        "StallWatchdog(1.0, startup_grace_s=0.0).start()\n"
        "time.sleep(30)\n"  # a 'wedged device call'; watchdog must kill us
        "print('unreachable')\n"
    )
    assert proc.returncode == watchdog.STALL_EXIT_CODE, (
        proc.returncode, proc.stderr,
    )
    assert "stall-watchdog" in proc.stderr
    assert "unreachable" not in proc.stdout


def test_beats_keep_it_alive_and_stop_disarms():
    # Generous timeout/beat ratio (15x): this watchdog is ARMED in the
    # pytest process, and a firing would os._exit the whole session —
    # the margin must absorb CI scheduler hiccups.
    w = watchdog.StallWatchdog(3.0, startup_grace_s=0.0).start()
    try:
        for _ in range(8):
            time.sleep(0.2)
            watchdog.beat()  # module-level beat reaches the armed instance
    finally:
        w.stop()
    assert w not in watchdog._ACTIVE
    time.sleep(0.5)  # disarmed: no exit even without beats


def test_cli_stall_timeout_clean_run(tmp_path):
    """--stall-timeout armed around a healthy run must not interfere."""
    proc = _run(
        "import sys\n"
        "sys.argv = ['train.py', '--algo', 'a2c', '--env', 'jax:two_state',\n"
        "            '--iterations', '3', '--quiet', '--log-every', '1',\n"
        f"            '--metrics', {str(tmp_path / 'm.jsonl')!r},\n"
        "            '--stall-timeout', '120']\n"
        "import train\n"
        "sys.exit(train.main())\n"
    )
    assert proc.returncode == 0, proc.stderr


def test_armed_and_ensure_timeout_at_least():
    """The chunk-wall auto-raise contract (ADVICE r4 #2): a completed
    chunk's measured wall time widens armed watchdogs, never narrows."""
    assert not watchdog.armed()
    w = watchdog.StallWatchdog(5.0, startup_grace_s=0.0).start()
    try:
        assert watchdog.armed()
        watchdog.ensure_timeout_at_least(2.0)   # below current: no-op
        assert w.timeout_s == 5.0
        watchdog.ensure_timeout_at_least(9.0)   # above: raises
        assert w.timeout_s == 9.0
        watchdog.ensure_timeout_at_least(9.0)   # equal: no-op
        assert w.timeout_s == 9.0
    finally:
        w.stop()
    assert not watchdog.armed()
    watchdog.ensure_timeout_at_least(99.0)      # disarmed: nothing to touch


def test_chunked_train_widens_watchdog_from_real_chunk_wall(monkeypatch):
    """End-to-end: checkpointed_train(stride>1) must measure the chunk
    BEHIND a block (a jitted call returns at enqueue time) and raise an
    armed watchdog to 3x the measured wall — from the SECOND dispatch on
    (the first is compile-inflated and skipped by design). Pinned to the
    HEURISTIC compile-detection path (telemetry listener off): these
    step fns fake compile latency with sleep, which the measured
    compile-event path correctly calls clean."""
    import jax.numpy as jnp

    from actor_critic_tpu.utils import checkpoint
    from actor_critic_tpu.utils.checkpoint import checkpointed_train

    monkeypatch.setattr(checkpoint, "_compile_probe", lambda: None)

    def slow_chunk(state, k):
        time.sleep(0.25)  # stand-in for real device wall time
        return state + k, {"loss": jnp.asarray(0.0)}

    # Default startup grace shields the FIRST chunk (in production it
    # shields first-call XLA compilation); the auto-raise must then widen
    # the armed 0.4s timeout past the 0.25s chunk wall before the grace
    # window would have expired. (An armed 0.1s/grace-0 variant of this
    # test correctly dies at the first chunk — that is the documented
    # pre-grace behavior, not a bug.)
    w = watchdog.StallWatchdog(0.4).start()
    try:
        state, _ = checkpointed_train(
            slow_chunk, jnp.asarray(0), num_iterations=4, stride=2,
        )
        assert int(state) == 4
        # 3 x ~0.25s measured wall (second dispatch): widened past 0.4.
        assert w.timeout_s >= 0.6, w.timeout_s
    finally:
        w.stop()


def test_chunked_train_first_dispatch_never_ratchets_and_wall_persists(
    tmp_path, monkeypatch
):
    """ISSUE 2 satellite: (a) the FIRST dispatch of a process — which in
    production carries full XLA compile — must not drive the auto-raise
    (it would bake compile time into 3x the stall timeout for the whole
    run); (b) the steady-state chunk wall persists to a ckpt-dir sidecar;
    (c) a resumed process widens its armed watchdog from the sidecar
    BEFORE its own (skipped) chunk 1. Heuristic detection path pinned
    (see test_chunked_train_widens_watchdog_from_real_chunk_wall)."""
    import json

    import jax.numpy as jnp

    from actor_critic_tpu.utils import checkpoint
    from actor_critic_tpu.utils.checkpoint import Checkpointer, checkpointed_train

    monkeypatch.setattr(checkpoint, "_compile_probe", lambda: None)

    calls = []

    def chunk(state, k):
        time.sleep(0.5 if not calls else 0.05)  # dispatch 1 "compiles"
        calls.append(k)
        return {"n": state["n"] + k}, {"loss": jnp.asarray(0.0)}

    init = {"n": jnp.asarray(0)}
    w = watchdog.StallWatchdog(0.4).start()  # default grace shields chunk 1
    try:
        with Checkpointer(tmp_path / "ck") as ck:
            state, _ = checkpointed_train(
                chunk, init, num_iterations=6, stride=2, ckpt=ck,
            )
        assert int(state["n"]) == 6 and len(calls) == 3
        # The 0.5s first dispatch did NOT ratchet (3 x 0.5 = 1.5 would
        # show); the 0.05s steady chunks ratchet 0.15 < 0.4 — a no-op.
        assert w.timeout_s == 0.4, w.timeout_s
    finally:
        w.stop()
    with open(tmp_path / "ck" / "chunk_wall.json") as f:
        wall = json.load(f)["chunk_wall_s"]
    assert 0 < wall < 0.3, wall  # steady wall, not the compile-inflated one

    # Resume leg: the persisted wall widens a narrower armed watchdog
    # before any dispatch runs (here: zero dispatches remain).
    w2 = watchdog.StallWatchdog(0.01).start()
    try:
        with Checkpointer(tmp_path / "ck") as ck:
            state, _ = checkpointed_train(
                chunk, init, num_iterations=6, stride=2, ckpt=ck,
            )
        assert int(state["n"]) == 6 and len(calls) == 3  # nothing re-ran
        assert w2.timeout_s >= 3.0 * wall - 1e-6, w2.timeout_s
    finally:
        w2.stop()


def test_chunked_train_ratchet_consumes_compile_events(monkeypatch):
    """ISSUE 4 satellite: with the telemetry compile listener installed,
    the ratchet decides "compile-inflated dispatch" from MEASURED compile
    events, not from per-k novelty — a recompile on a later same-k
    dispatch (the storm case the heuristic misreads as a clean wall)
    must extend grace instead of ratcheting its inflated wall into the
    permanent timeout."""
    import jax.numpy as jnp

    from actor_critic_tpu.utils import checkpoint
    from actor_critic_tpu.utils.checkpoint import checkpointed_train

    compile_count = [0]
    monkeypatch.setattr(
        checkpoint, "_compile_probe", lambda: (lambda: compile_count[0])
    )
    calls = []

    def chunk(state, k):
        calls.append(k)
        if len(calls) <= 2:
            compile_count[0] += 1  # dispatches 1 AND 2 "pay compile"
            time.sleep(0.3)       # compile-inflated wall
        else:
            time.sleep(0.05)      # steady-state wall
        return state + k, {"loss": jnp.asarray(0.0)}

    w = watchdog.StallWatchdog(0.4).start()
    try:
        state, _ = checkpointed_train(
            chunk, jnp.asarray(0), num_iterations=6, stride=2,
        )
        assert int(state) == 6 and len(calls) == 3
        # The k-novelty heuristic would have ratcheted dispatch 2
        # (same k as dispatch 1) to 3 x 0.3 = 0.9s; the event-driven
        # path shields it, and the clean 0.05s dispatch ratchets a
        # no-op 0.15 < 0.4.
        assert w.timeout_s == 0.4, w.timeout_s
    finally:
        w.stop()
