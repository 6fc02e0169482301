#!/usr/bin/env python3
"""The load generator: a process of its own that never imports jax.

A copy of the closed loop of `scripts/serve_loadgen.py` (one keep-alive
connection per client, Nagle off, each client waits for its reply and sends
again), changed in what a yardstick needs: request bodies are drawn from
`--seed` (standard-normal observations, a ring of distinct bodies per client,
not one fixed body), clients come in groups with their own rows per request
(the traffic file's `clients`), only requests that complete inside the window
are counted, and the window starts when the parent says so.

Protocol with the parent: prints `READY` once every client has connected,
waits for a line on standard input, measures for `--seconds`, prints one JSON
line and exits. Latency is send to full reply, on this process's monotonic
clock. An open-loop mix (arrival times in the traffic file, latency from the
due instant) would be a second `mode` of this same generator.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import random
import socket
import sys
import threading
import time
from urllib.parse import urlparse


def percentile(sorted_vals: list, p: float) -> float:
    """Nearest-rank percentile of a sorted list (0.0 when empty)."""
    if not sorted_vals:
        return 0.0
    k = max(0, min(len(sorted_vals) - 1,
                   math.ceil(p / 100.0 * len(sorted_vals)) - 1))
    return float(sorted_vals[k])


def make_bodies(rng: random.Random, rows: int, obs_dim: int, n: int) -> list[bytes]:
    return [
        json.dumps({"obs": [[round(rng.gauss(0.0, 1.0), 4) for _ in range(obs_dim)]
                            for _ in range(rows)]}).encode()
        for _ in range(n)
    ]


class Client(threading.Thread):
    def __init__(self, url: str, rows: int, bodies: list[bytes], timeout_s: float,
                 go: threading.Event, window: list):
        super().__init__(daemon=True)
        self.parsed = urlparse(url)
        self.rows, self.bodies, self.timeout_s = rows, bodies, timeout_s
        self.go, self.window = go, window
        self.lat_ms: list[float] = []
        self.sent = 0
        self.failed = 0
        self.conn = None

    def connect(self) -> None:
        c = http.client.HTTPConnection(
            self.parsed.hostname, self.parsed.port, timeout=self.timeout_s)
        c.connect()
        c.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.conn = c

    def run(self) -> None:
        headers = {"Content-Type": "application/json"}
        self.go.wait()
        t_start, t_end = self.window
        k = 0
        while True:
            t0 = time.monotonic()
            if t0 >= t_end:
                return
            body = self.bodies[k % len(self.bodies)]
            k += 1
            self.sent += 1
            ok = False
            try:
                if self.conn is None:
                    self.connect()
                self.conn.request("POST", "/v1/act", body=body, headers=headers)
                resp = self.conn.getresponse()
                payload = resp.read()
                if resp.status == 200:
                    ok = len(json.loads(payload)["actions"]) == self.rows
                if resp.will_close:
                    self.conn.close()
                    self.conn = None
            except Exception:
                try:
                    self.conn.close()
                except Exception:
                    pass
                self.conn = None
            t1 = time.monotonic()
            if t1 > t_end:
                # Completed after the window: neither a success nor a
                # failure of the window.
                self.sent -= 1
                return
            if ok:
                self.lat_ms.append((t1 - t0) * 1e3)
            else:
                self.failed += 1
                time.sleep(0.01)


def run(url: str, traffic: dict, seed: int, seconds: float, obs_dim: int,
        wait_for_go=None) -> dict:
    rng = random.Random(seed)
    go = threading.Event()
    window = [0.0, 0.0]
    clients = []
    for group in traffic["clients"]:
        for _ in range(int(group["count"])):
            bodies = make_bodies(rng, int(group["rows"]), obs_dim,
                                 int(traffic.get("bodies_per_client", 32)))
            clients.append(Client(url, int(group["rows"]), bodies,
                                  float(traffic.get("timeout_s", 10.0)), go, window))
    for c in clients:
        c.connect()
        c.start()
    if wait_for_go is not None:
        wait_for_go()
    window[0] = time.monotonic()
    window[1] = window[0] + seconds
    go.set()
    for c in clients:
        c.join(seconds + float(traffic.get("timeout_s", 10.0)) + 5.0)
    lat = sorted(x for c in clients for x in c.lat_ms)
    ok_rows = sum(len(c.lat_ms) * c.rows for c in clients)
    return {
        "mode": "closed",
        "clients": len(clients),
        "attempted": sum(c.sent for c in clients),
        "failed": sum(c.failed for c in clients),
        "ok_requests": len(lat),
        "ok_rows": ok_rows,
        "seconds": seconds,
        "act_per_s": ok_rows / seconds,
        "requests_per_s": len(lat) / seconds,
        "p50_ms": percentile(lat, 50),
        "p99_ms": percentile(lat, 99),
        "max_ms": lat[-1] if lat else 0.0,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--url", required=True)
    p.add_argument("--traffic", required=True, help="traffic file (JSON)")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--obs-dim", type=int, required=True)
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)
    with open(args.traffic) as fh:
        traffic = json.load(fh)
    if args.rehearsal:
        traffic.update(traffic.get("rehearsal", {}))

    def wait_for_go():
        print("READY", flush=True)
        sys.stdin.readline()

    print(json.dumps(run(args.url, traffic, args.seed, args.seconds,
                         args.obs_dim, wait_for_go)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
