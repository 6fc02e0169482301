"""chip_smoke.py — the standing proof that the system starts on the chip.

Drives the main path once, on one TPU chip (and the dp path when the host
has more), through `train.main` at the width of presets the repo ships,
and checks what comes out by the repo's own means:

  kernels  compiled Pallas gae / lambda_returns / vtrace against the
           lax.scan references (ops/returns.py) at seven shapes
  A        fused pixel IMPALA: Nature CNN on 36-px Pong, E=64, T=20,
           chunked full-stride program + eval (V-trace kernel)
  B        fused A2C CartPole, E=4096, T=64 (GAE kernel at full lanes)
  C        PPO HalfCheetah learner + device data plane + resident gateway
           in one process, answering /v1/act while it trains
  dp       (device_count > 1) A2C under make_dp_train_step at E=4096,
           sharded-ring TD3, sp x dp IMPALA on the real devices

One process, the only one that touches JAX: a chip belongs to one process.
Every leg asserts finite losses and an advanced step counter; A-C also
assert the Mosaic kernel is in a compiled program and that no AOT warmup
compile failed. A leg that fails raises, so the process exits non-zero;
nothing here catches a leg's exception.

    python chip_smoke.py                 # on a machine with a TPU
    python chip_smoke.py --expect-warm   # second run: the step, eval and
                                         # act programs must all come from
                                         # the persistent compile cache

Without a TPU it prints why and exits 2, with no result line. The last
line of a passing run is the one JSON object
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.

`--rehearsal` is a control-flow check on the CPU at tiny sizes for whoever
edits this file: every line it prints says `REHEARSAL platform=cpu`, it
skips what only a chip can show (kernel engagement, device memory) and it
never prints a result line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
REHEARSAL_TAG = "REHEARSAL platform=cpu"

KERNEL_SHAPES = ((1, 7), (20, 64), (64, 4096), (256, 4), (32, 96), (32, 200), (1024, 256))
KERNEL_TOL = 1e-5  # float32; the chip has so far matched lax.scan bit for bit

# train.main argument lists, at the presets' own sizes. Rehearsal appends
# shrinking overrides; the chip never does.
LEG_A = ["--preset", "impala_pong_learn", "--iterations", "40", "--chunk", "20",
         "--eval-every", "20"]
LEG_B = ["--preset", "a2c_cartpole", "--iterations", "20", "--chunk", "10"]
LEG_C = ["--preset", "ppo_halfcheetah", "--async-actors", "2", "--data-plane",
         "device", "--iterations", "6"]
REHEARSAL_SHRINK = {
    "A": ["--iterations", "4", "--chunk", "2", "--eval-every", "2",
          "--set", "num_envs=8"],
    "B": ["--iterations", "4", "--chunk", "2", "--set", "num_envs=64",
          "--set", "rollout_steps=8"],
    "C": ["--iterations", "3", "--set", "rollout_steps=32", "--set", "epochs=2",
          "--set", "num_minibatches=4"],
}

# Compiled-program (MLIR module) names per leg: which must carry the
# Mosaic kernel, and which a second run must load from the cache.
PROGRAMS = {
    "A": {"kernel": ("jit_full",), "cached": ("jit_full", "jit_eval_fn")},
    "B": {"kernel": ("jit_full",), "cached": ("jit_full",)},
    "C": {"kernel": ("jit_device_update",),
          "cached": ("jit_device_update", "jit_act")},
}


class Smoke:
    """One run's settings and the bookkeeping shared by the legs."""

    def __init__(self, rehearsal: bool, expect_warm: bool):
        self.rehearsal = rehearsal
        self.expect_warm = expect_warm
        self._warmups_seen = 0

    # -- observation of what train.main compiled ---------------------------
    def check_warmups(self, leg: str) -> None:
        """No AOT warmup compile of this leg failed (the warmup thread
        contains its exceptions: compile_cache.WarmupRunner._run)."""
        from actor_critic_tpu.utils import compile_cache

        runners = compile_cache.started_warmups()[self._warmups_seen:]
        self._warmups_seen += len(runners)
        assert runners, f"leg {leg}: train.main started no AOT warmup"
        for runner in runners:
            assert runner.wait(timeout=600), f"leg {leg}: AOT warmup still running"
            errors = [r for r in runner.results if "error" in r]
            assert not errors, f"leg {leg}: AOT warmup compile failed: {errors}"
            print(f"leg {leg}: warmup ok " + ", ".join(
                f"{r['entry']}={r['compile_s']}s" for r in runner.results))

    def check_programs(self, leg: str, records: list[dict]) -> None:
        by_name: dict[str, list[dict]] = {}
        for r in records:
            by_name.setdefault(r["name"], []).append(r)
        for name in sorted(by_name):
            rs = by_name[name]
            if name in PROGRAMS[leg]["cached"] or any(r.get("mosaic_calls") for r in rs):
                print(
                    f"leg {leg}: program {name} x{len(rs)} "
                    f"cache_hits={sum(bool(r.get('cache_hit')) for r in rs)} "
                    f"mosaic_calls={max(r.get('mosaic_calls', 0) for r in rs)} "
                    f"compile_s={[r['compile_s'] for r in rs]}"
                )
        for name in PROGRAMS[leg]["cached"]:
            assert name in by_name, (
                f"leg {leg}: no compile of {name}; saw {sorted(by_name)}"
            )
            if self.expect_warm:
                missed = [r for r in by_name[name] if not r.get("cache_hit")]
                assert not missed, (
                    f"leg {leg}: --expect-warm but {name} compiled instead of "
                    f"hitting the persistent cache: {missed}"
                )
        if not self.rehearsal:
            for name in PROGRAMS[leg]["kernel"]:
                assert all(r.get("mosaic_calls") for r in by_name[name]), (
                    f"leg {leg}: {name} carries no Mosaic custom call — the "
                    "Pallas kernel is not in the compiled program"
                )

    # -- one train.main leg -------------------------------------------------
    def train_leg(self, leg: str, argv: list[str]) -> list[dict]:
        """`train.main(argv)` in-process, then the checks every leg shares.
        Returns the metric rows it logged."""
        import train
        from actor_critic_tpu.telemetry import profiler
        from actor_critic_tpu.utils import compile_cache

        metrics_path = os.path.join(OUT_DIR, f"leg_{leg}.jsonl")
        if os.path.exists(metrics_path):
            os.remove(metrics_path)
        argv = [*argv, "--metrics", metrics_path]
        if self.rehearsal:
            argv += REHEARSAL_SHRINK[leg]
        print(f"leg {leg}: train.py {' '.join(argv)}")
        count0 = profiler.compile_event_count()
        stats0 = compile_cache.cache_stats()
        t0 = time.perf_counter()
        rc = train.main(argv)
        wall = time.perf_counter() - t0
        assert rc == 0, f"leg {leg}: train.main returned {rc}"

        self.check_warmups(leg)
        self.check_programs(leg, profiler.compile_records_since(count0))
        stats1 = compile_cache.cache_stats()
        with open(metrics_path) as f:
            rows = [json.loads(line) for line in f]
        # argparse keeps the LAST --iterations (rehearsal appends one).
        iterations = int([v for k, v in zip(argv, argv[1:]) if k == "--iterations"][-1])
        assert rows and rows[-1]["iter"] == iterations, (
            f"leg {leg}: step counter stopped at "
            f"{rows[-1]['iter'] if rows else None}, wanted {iterations}"
        )
        losses = {k: r[k] for r in rows for k in r if "loss" in k}
        assert losses, f"leg {leg}: no loss in the metric rows {rows}"
        for r in rows:
            for k, v in r.items():
                if "loss" in k:
                    # JsonlLogger writes a non-finite value as null.
                    assert isinstance(v, (int, float)) and math.isfinite(v), (
                        f"leg {leg}: non-finite {k}={v!r} at iter {r['iter']}"
                    )
        print(
            f"leg {leg} ok: iter={rows[-1]['iter']} "
            + " ".join(f"{k}={v:.5g}" for k, v in sorted(losses.items()))
            + f" | cache hits +{stats1['hits'] - stats0['hits']} "
            f"misses +{stats1['misses'] - stats0['misses']} | wall {wall:.1f}s "
            "(compile included; not a speed)"
        )
        return rows


# ---------------------------------------------------------------- the legs
def leg_kernels(smoke: Smoke) -> None:
    """Compiled kernels against the lax.scan references, with engagement
    asserted first so a fall-back to lax.scan cannot pass as the kernel."""
    import jax
    import numpy as np

    from actor_critic_tpu.ops import pallas_scan, returns
    from actor_critic_tpu.telemetry import profiler

    gamma, lam = 0.99, 0.95
    for T, E in KERNEL_SHAPES:
        rng = np.random.default_rng(T * 100003 + E)
        f32 = lambda *shape: jax.numpy.asarray(rng.normal(size=shape), "float32")
        rewards, values, boot = f32(T, E), f32(T, E), f32(E)
        dones = jax.numpy.asarray(rng.random((T, E)) < 0.1, "float32")
        tlp, blp = 0.5 * f32(T, E), 0.5 * f32(T, E)
        cases = {
            "gae": (
                lambda *a: pallas_scan.gae(*a, gamma, lam),
                lambda *a: returns.gae(*a, gamma, lam),
                (rewards, values, dones, boot),
            ),
            "lambda": (
                lambda *a: pallas_scan.lambda_returns(*a, gamma, lam),
                lambda *a: returns.lambda_returns(*a, gamma, lam),
                (rewards, values, dones, boot),
            ),
            "vtrace": (
                lambda *a: tuple(pallas_scan.vtrace(*a, gamma)),
                lambda *a: tuple(returns.vtrace(*a, gamma)),
                (tlp, blp, rewards, values, dones, boot),
            ),
        }
        worst = {}
        for op, (kernel_fn, ref_fn, args) in cases.items():
            block = pallas_scan.kernel_block(op, T, E)
            assert block > 0, f"{op} (T={T}, E={E}): kernel_block == 0, lax.scan fallback"
            count0 = profiler.compile_event_count()
            got = jax.jit(kernel_fn)(*args)
            jax.block_until_ready(got)
            mosaic = sum(
                r.get("mosaic_calls", 0)
                for r in profiler.compile_records_since(count0)
            )
            assert mosaic or smoke.rehearsal, (
                f"{op} (T={T}, E={E}): no Mosaic custom call in the compiled program"
            )
            want = jax.jit(ref_fn)(*args)
            err = 0.0
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                g, w = np.asarray(g), np.asarray(w)
                assert g.shape == w.shape and np.isfinite(g).all(), (op, T, E, g.shape)
                np.testing.assert_allclose(
                    g, w, rtol=KERNEL_TOL, atol=KERNEL_TOL,
                    err_msg=f"{op} kernel vs lax.scan at (T={T}, E={E})",
                )
                err = max(err, float(np.max(np.abs(g - w))))
            worst[op] = (block, mosaic, err)
        print(f"kernels (T={T}, E={E}) ok: " + " ".join(
            f"{op}[block={b} mosaic_calls={m} max_abs_err={e:.1e}]"
            for op, (b, m, e) in worst.items()))


def leg_a(smoke: Smoke) -> None:
    rows = smoke.train_leg("A", LEG_A)
    evals = [r["eval_return"] for r in rows if "eval_return" in r]
    assert evals and all(isinstance(v, (int, float)) and math.isfinite(v) for v in evals), (
        f"leg A: the eval program returned {evals}"
    )
    print(f"leg A: eval_return {evals}")


def leg_b(smoke: Smoke) -> None:
    smoke.train_leg("B", LEG_B)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ActClient(threading.Thread):
    """POSTs /v1/act batches of 1 and 5 rows to the resident gateway for as
    long as leg C trains (5 rows backfill into the next bucket of the
    sidecar's 1,4,16 ladder). Records every response; judges nothing itself
    (an assertion on this thread would not reach the process exit code)."""

    def __init__(self, port: int, obs_dim: int):
        super().__init__(name="chip-smoke-act-client", daemon=True)
        self.url = f"http://127.0.0.1:{port}/v1/act"
        self.obs_dim = obs_dim
        self.stop = threading.Event()
        self.responses: list[tuple[int, int, dict]] = []  # (rows, status, body)
        self.errors: list[str] = []

    def run(self) -> None:
        import numpy as np

        rng = np.random.default_rng(0)
        n_sent = 0
        while not self.stop.is_set():
            rows = (1, 5)[n_sent % 2]
            body = json.dumps({"obs": rng.normal(size=(rows, self.obs_dim)).tolist()})
            req = urllib.request.Request(
                self.url, data=body.encode(),
                headers={"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    self.responses.append((rows, resp.status, json.load(resp)))
                n_sent += 1
            except urllib.error.HTTPError as e:
                self.responses.append((rows, e.code, {"error": e.read().decode()[:300]}))
                n_sent += 1
            except (urllib.error.URLError, ConnectionError, TimeoutError) as e:
                # Not up yet (before the first answer) or already closed
                # (train.main's finally closes the gateway before this
                # thread is told to stop): neither is a served request.
                if self.responses:
                    self.errors.append(repr(e))
            time.sleep(0.05)


def leg_c(smoke: Smoke) -> None:
    import numpy as np

    port = _free_port()
    client = ActClient(port, obs_dim=17)  # HalfCheetah-v5 observation width
    client.start()
    try:
        smoke.train_leg("C", [*LEG_C, "--serve-port", str(port)])
    finally:
        client.stop.set()
        client.join(timeout=60)
    assert not client.is_alive(), "leg C: the act client did not stop"

    ok = [(rows, body) for rows, status, body in client.responses if status == 200]
    bad = [(rows, status, body) for rows, status, body in client.responses if status != 200]
    assert not bad, f"leg C: /v1/act answered non-200: {bad[:3]}"
    assert {rows for rows, _ in ok} == {1, 5}, (
        f"leg C: wanted answered batches of 1 and 5 rows, got {len(ok)} answers"
    )
    for rows, body in ok:
        actions = np.asarray(body["actions"], np.float32)
        assert actions.shape == (rows, 6) and np.isfinite(actions).all(), (
            f"leg C: bad actions for a {rows}-row request: {body}"
        )
    versions = [body["version"] for _, body in ok]
    assert versions == sorted(versions) and versions[-1] > versions[0], (
        f"leg C: served version did not rise while training: {versions}"
    )
    print(
        f"leg C: served {len(ok)} /v1/act requests while training "
        f"(rows 1 and 5, version {versions[0]} -> {versions[-1]}, "
        f"{len(client.errors)} after the gateway closed)"
    )
    # The act program compiled for the device (check_programs saw jit_act,
    # which the numpy mirror never compiles), so the engine did not resolve
    # to `mirror`; the log line "warm: 3 act buckets" says the same.


def leg_dp(smoke: Smoke) -> None:
    import jax

    import __graft_entry__ as graft

    devices = jax.devices()
    n = len(devices)
    num_envs = 8 * n if smoke.rehearsal else 4096  # the a2c_cartpole preset's E
    out = graft.multichip_steps(devices, a2c_num_envs=num_envs)
    # multichip_steps asserted the placement (graft.check_dp_placement):
    # num_envs/n rows of every env-batch leaf on each device, params on all.
    # out["a2c_state"] keeps that state alive for the memory reading.
    obs = out["a2c_state"].rollout.obs
    print("dp: env-batch obs rows per device " + str(
        {s.device.id: s.data.shape[0] for s in obs.addressable_shards}))
    if not smoke.rehearsal:
        for d in devices:
            in_use = d.memory_stats()["bytes_in_use"]
            assert in_use > 0, f"device {d} holds nothing after the dp steps"
            print(f"dp: device {d.id} bytes_in_use={in_use}")
    print(
        f"dp ok on {n} devices: A2C E={num_envs} ({num_envs // n} rows/device, "
        f"params replicated) loss={out['a2c_loss']:.4f} | sharded-ring TD3 "
        f"critic_loss={out['td3_critic_loss']:.4f} | sp{out['sp']} x dp{out['dp']} "
        f"IMPALA loss={out['sp_loss']:.4f}"
    )


def default_legs() -> list:
    import jax

    legs = [("kernels", leg_kernels), ("A", leg_a), ("B", leg_b), ("C", leg_c)]
    if jax.device_count() > 1:
        legs.append(("dp", leg_dp))
    return legs


def run_legs(smoke: Smoke, legs: list) -> None:
    """Each leg in order. Deliberately no try/except: a leg that raises
    ends the process with a traceback and a non-zero exit code."""
    for name, leg in legs:
        print(f"=== leg {name} ===")
        leg(smoke)


class _TaggedStdout:
    """Prefix every line written to stdout (rehearsal only)."""

    def __init__(self, stream, tag: str):
        self._stream, self._tag, self._bol = stream, tag, True

    def write(self, text: str) -> int:
        for chunk in text.splitlines(keepends=True):
            if self._bol:
                self._stream.write(self._tag + " ")
            self._stream.write(chunk)
            self._bol = chunk.endswith("\n")
        return len(text)

    def __getattr__(self, name):
        return getattr(self._stream, name)


def main(argv=None, legs=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--expect-warm", action="store_true",
                   help="fail unless the step, eval and act programs load "
                   "from the persistent compile cache (a second run)")
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU control-flow check at tiny sizes; prints no result")
    args = p.parse_args(argv)
    if not __debug__:
        # Every check here and in __graft_entry__ is an `assert`.
        print("chip_smoke: python -O strips the checks; run without it", file=sys.stderr)
        return 2

    import jax

    device = jax.devices()[0]
    if args.rehearsal:
        if device.platform != "cpu":
            print(f"--rehearsal is a CPU check; this is {device.platform}", file=sys.stderr)
            return 2
        sys.stdout = _TaggedStdout(sys.stdout, REHEARSAL_TAG)
    print(
        f"jax {jax.__version__} devices={jax.devices()} "
        f"platform={device.platform} device_kind={device.device_kind} "
        f"count={jax.device_count()}"
    )
    if device.platform != "tpu" and not args.rehearsal:
        print(
            f"chip_smoke: JAX found no TPU (platform={device.platform}); this "
            "check only means something on the chip. No result.",
            file=sys.stderr,
        )
        return 2

    from actor_critic_tpu.telemetry import profiler
    from actor_critic_tpu.utils import compile_cache

    os.makedirs(OUT_DIR, exist_ok=True)
    assert profiler.ensure_compile_introspection(), "jax compile funnel moved"
    cache_dir = compile_cache.enable_persistent_cache(compile_cache.resolve_cache_dir())
    print(f"compile cache: {cache_dir} (expect_warm={args.expect_warm})")

    smoke = Smoke(rehearsal=args.rehearsal, expect_warm=args.expect_warm)
    t0 = time.perf_counter()
    try:
        run_legs(smoke, default_legs() if legs is None else legs)
    finally:
        if args.rehearsal:
            sys.stdout = sys.stdout._stream
    stats = compile_cache.cache_stats()
    print(
        (f"{REHEARSAL_TAG} " if args.rehearsal else "")
        + f"all legs ok in {time.perf_counter() - t0:.0f}s (compile included) | "
        f"persistent cache hits={stats['hits']} misses={stats['misses']}"
    )
    if args.rehearsal:
        print(f"{REHEARSAL_TAG} passed; a rehearsal is not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count(),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
