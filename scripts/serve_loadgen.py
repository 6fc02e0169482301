#!/usr/bin/env python
"""Load generator for the policy-serving gateway (ISSUE 10/17).

    python scripts/serve_loadgen.py --url http://127.0.0.1:8000 \
        --concurrency 16 --duration 10 --obs-dim 4 [--rows 1] [--json]

    # open-loop: 200 requests/s fixed arrival schedule (ISSUE 17)
    python scripts/serve_loadgen.py --url ... --rate 200 --duration 10

Closed loop (default): N worker threads each POST /v1/act, wait for the
reply, repeat — over ONE keep-alive connection each, so measured
latency is the gateway's (queue wait + micro-batch window + dispatch),
not TCP setup. Closed-loop at saturating concurrency is the SLO-bench
shape: offered load adapts to service rate, and p50/p99 come from the
per-request walls the workers record.

Open loop (`--rate R`): arrivals are pinned to a fixed schedule —
request k fires at `k / R` seconds regardless of how the previous one
fared (worker w takes arrivals w, w+C, w+2C, ...). Offered load does
NOT adapt, so saturation shows up as queueing/shedding instead of a
silently slowed generator: `late` counts arrivals that fired behind
schedule (every connection busy past its slot — the open-loop
saturation signal), and 503s are split into `shed` (the gateway's
admission-control answer, body `shed: true`) vs plain `rejected_503`
(queue-full). `run_load` is the library entry."""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import socket
import sys
import threading
import time
from urllib.parse import urlparse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _percentile(sorted_vals: list, p: float) -> float:
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   math.ceil(p / 100.0 * len(sorted_vals)) - 1))
    return float(sorted_vals[k])


def _worker(
    url: str,
    body: bytes,
    rows: int,
    deadline: float,
    timeout_s: float,
    out: dict,
    start: threading.Event,
    arrivals: tuple | None = None,
) -> None:
    """One load worker. `arrivals=None` is the closed loop; an
    `(offset_s, step_s)` pair is this worker's slice of the open-loop
    schedule: its k-th request fires at `start + offset + k*step`."""
    parsed = urlparse(url)
    lat_ms: list[float] = []
    errors = 0
    late = 0
    shed = 0
    rejected_503 = 0

    def connect() -> http.client.HTTPConnection:
        c = http.client.HTTPConnection(
            parsed.hostname, parsed.port, timeout=timeout_s
        )
        c.connect()
        # Nagle off, matching the gateway handler: small POST bodies
        # otherwise pay the ~40 ms delayed-ACK stall per round trip.
        c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return c

    conn = None
    headers = {"Content-Type": "application/json"}
    start.wait()
    t_base = time.monotonic()
    k = 0
    try:
        while time.monotonic() < deadline:
            if arrivals is not None:
                # Fixed-arrival-rate pacing: sleep until this worker's
                # next slot; firing past it means the previous request
                # overran — the open-loop saturation signal.
                t_next = t_base + arrivals[0] + k * arrivals[1]
                if t_next >= deadline:
                    break
                now = time.monotonic()
                if t_next > now:
                    time.sleep(t_next - now)
                else:
                    late += 1
                k += 1
            if conn is None:
                # Inside the loop and counted: a dead/refusing gateway
                # must surface as errors, not kill the worker before it
                # records anything (a zero-request, zero-error result
                # would read as a clean measurement).
                try:
                    conn = connect()
                except Exception:
                    errors += 1
                    time.sleep(0.05)
                    continue
            t0 = time.monotonic()
            try:
                conn.request("POST", "/v1/act", body=body, headers=headers)
                resp = conn.getresponse()
                payload = resp.read()  # must drain for keep-alive reuse
                if resp.will_close:
                    # HTTP/1.0 server (the sequential baseline): no
                    # keep-alive — reconnect per request, which is part
                    # of that architecture's cost; the reconnect happens
                    # at the top of the next iteration.
                    conn.close()
                    conn = None
                if resp.status != 200:
                    errors += 1
                    if resp.status == 503:
                        # Discriminate the gateway's two 503 classes
                        # (ISSUE 17): admission-control shed marks its
                        # body; a plain 503 is queue-full/down.
                        try:
                            if json.loads(payload).get("shed"):
                                shed += 1
                            else:
                                rejected_503 += 1
                        except Exception:
                            rejected_503 += 1
                    continue
                json.loads(payload)
            except Exception:
                errors += 1
                # The connection state is unknown after a failure;
                # drop it and let the loop top rebuild (counted there
                # if the gateway is down).
                try:
                    conn.close()
                except Exception:
                    pass
                conn = None
                continue
            lat_ms.append((time.monotonic() - t0) * 1e3)
    finally:
        if conn is not None:
            try:
                conn.close()
            except Exception:
                pass
        # Inside finally: even an unexpected worker death must leave
        # its partial tallies readable instead of a silent clean zero.
        out["lat_ms"] = lat_ms
        out["errors"] = errors
        out["late"] = late
        out["shed"] = shed
        out["rejected_503"] = rejected_503
        out["rows"] = rows


def run_load(
    url: str,
    concurrency: int = 16,
    duration_s: float = 10.0,
    obs=None,
    obs_dim: int = 4,
    rows: int = 1,
    policy: str | None = None,
    timeout_s: float = 30.0,
    rate: float | None = None,
) -> dict:
    """Drive the gateway; returns the SLO summary (requests,
    actions_per_s, p50/p99/max ms, errors). `obs` overrides the
    generated [rows, obs_dim] zero observation batch. `rate` switches
    to the open loop: requests/s offered on a fixed arrival schedule
    striped across the workers (module docstring)."""
    if rate is not None and rate <= 0:
        raise ValueError(f"rate must be > 0 req/s, got {rate!r}")
    if obs is None:
        obs = [[0.1] * obs_dim for _ in range(rows)]
    body_obj: dict = {"obs": obs}
    if policy is not None:
        body_obj["policy"] = policy
    body = json.dumps(body_obj).encode()
    start = threading.Event()
    deadline = time.monotonic() + duration_s
    results: list[dict] = [{} for _ in range(concurrency)]
    threads = [
        threading.Thread(
            target=_worker,
            args=(url, body, rows, deadline, timeout_s, results[i], start,
                  None if rate is None
                  else (i / rate, concurrency / rate)),
            name=f"loadgen-{i}",
            daemon=True,
        )
        for i in range(concurrency)
    ]
    for t in threads:
        t.start()
    t_start = time.monotonic()
    start.set()
    for t in threads:
        t.join(duration_s + timeout_s + 10)
    wall = time.monotonic() - t_start
    lat = sorted(x for r in results for x in r.get("lat_ms", []))
    requests = len(lat)
    errors = sum(r.get("errors", 0) for r in results)
    return {
        "mode": "closed" if rate is None else "open",
        "requests": requests,
        "errors": errors,
        "late": sum(r.get("late", 0) for r in results),
        "shed": sum(r.get("shed", 0) for r in results),
        "rejected_503": sum(r.get("rejected_503", 0) for r in results),
        "offered_per_s": None if rate is None else float(rate),
        "wall_s": round(wall, 3),
        "requests_per_s": round(requests / wall, 2) if wall > 0 else 0.0,
        "actions_per_s": round(requests * rows / wall, 2) if wall > 0 else 0.0,
        "p50_ms": round(_percentile(lat, 50), 3),
        "p99_ms": round(_percentile(lat, 99), 3),
        "max_ms": round(lat[-1], 3) if lat else 0.0,
        "config": {
            "concurrency": concurrency,
            "duration_s": duration_s,
            "rows": rows,
            "rate": rate,
        },
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[1].strip())
    p.add_argument("--url", required=True, help="gateway base URL")
    p.add_argument("--concurrency", type=int, default=16)
    p.add_argument("--duration", type=float, default=10.0, metavar="S")
    p.add_argument(
        "--obs-dim", type=int, default=4,
        help="flat observation dimension of the generated payload",
    )
    p.add_argument(
        "--rows", type=int, default=1,
        help="observations per request (default 1 — the GA3C shape)",
    )
    p.add_argument("--policy", default=None, help="policy id to route to")
    p.add_argument("--timeout", type=float, default=30.0)
    p.add_argument(
        "--rate", type=float, default=None, metavar="R",
        help="open-loop mode: offer R requests/s on a fixed arrival "
        "schedule striped across --concurrency connections (default: "
        "closed loop — each worker waits for its reply)",
    )
    p.add_argument("--json", action="store_true", help="machine output")
    args = p.parse_args(argv)
    out = run_load(
        args.url,
        concurrency=args.concurrency,
        duration_s=args.duration,
        obs_dim=args.obs_dim,
        rows=args.rows,
        policy=args.policy,
        timeout_s=args.timeout,
        rate=args.rate,
    )
    if args.json:
        print(json.dumps(out))
    else:
        extra = (
            f"; offered {out['offered_per_s']}/s, late {out['late']}, "
            f"shed {out['shed']}, rejected {out['rejected_503']}"
            if out["mode"] == "open" else ""
        )
        print(
            f"{out['requests']} requests ({out['errors']} errors) in "
            f"{out['wall_s']}s -> {out['actions_per_s']} actions/s; "
            f"p50 {out['p50_ms']} ms, p99 {out['p99_ms']} ms, "
            f"max {out['max_ms']} ms{extra}"
        )
    return 0 if out["errors"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
