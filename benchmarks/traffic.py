"""The one load generator: reads a traffic mix's parameters, sends the queries.

Runs as a child of the driver, in a process that never imports JAX, so the
process that holds the chip does no client work:

    python -m benchmarks.traffic <spec.json>      (from the checkout's root)

The spec holds the cell's traffic parameters, the seed, the port and the
window's length. Protocol on the pipes: the child prepares its schedule and
its keep-alive connections and writes ``ready``; the parent writes ``go``;
the child runs the window, writes its record to ``spec["out"]`` and exits.

Parameters of a mix (``workloads/<cell>.json``, ``traffic``):

- ``loop``: ``open`` sends on a schedule whatever the server does, and times
  each request from the instant it was due; ``closed`` has ``clients``
  connections that each send their next query when the last has returned.
- ``rate_per_s`` (open): Poisson arrivals, the gaps drawn from the seed.
- ``burst`` (open, optional): ``{"on_s", "off_s", "factor"}``: the rate is
  ``factor`` x ``rate_per_s`` for ``on_s`` seconds, then nothing for
  ``off_s``, repeated.
- ``connections`` (open) / ``clients`` (closed): keep-alive sockets.
- ``users``: ``{"zipf": a}`` draws user rows rank-by-rank from a Zipf law
  over the configuration's users; ``unknown_share`` of the queries name a
  user the model has never seen.
- ``num``: how many items a query asks for.

The raw-socket client is ``tools/serving_bench.py``'s (PERF.md, inventory).
"""

from __future__ import annotations

import json
import socket
import sys
import threading
import time

import numpy as np

from benchmarks.seeded import rng_for, user_id

UNKNOWN_USER = -1


def zipf_rows(n: int, size: int, a: float, rng) -> np.ndarray:
    """``size`` rows of ``range(n)``, row ``r`` with weight ``(r + 1) ** -a``."""
    weights = np.arange(1, n + 1, dtype=np.float64) ** -a
    cdf = np.cumsum(weights)
    return np.searchsorted(cdf, rng.random(size) * cdf[-1]).astype(np.int64)


def draw_users(traffic: dict, n_users: int, size: int, seed: int) -> np.ndarray:
    """User rows of ``size`` queries; ``UNKNOWN_USER`` marks an unseen user."""
    rng = rng_for(seed, 10)
    rows = zipf_rows(n_users, size, traffic["users"]["zipf"], rng)
    # rank is not row: spread the popular ranks over the table
    rows = (rows * 2654435761) % n_users
    unknown = rng.random(size) < traffic.get("unknown_share", 0.0)
    rows[unknown] = UNKNOWN_USER
    return rows


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times of an open loop, seconds from the window's start."""
    rng = rng_for(seed, 11)
    rate = float(traffic["rate_per_s"])
    burst = traffic.get("burst")
    if not burst:
        n = int(rate * seconds * 1.2) + 64
        due = np.cumsum(rng.exponential(1.0 / rate, size=n))
        return due[due < seconds]
    on, off, factor = burst["on_s"], burst["off_s"], burst["factor"]
    n = int(rate * factor * seconds * 1.2) + 64
    busy = np.cumsum(rng.exponential(1.0 / (rate * factor), size=n))
    due = busy + np.floor(busy / on) * off  # busy time -> wall time
    return due[due < seconds]


def body_for(row: int, num: int) -> bytes:
    user = user_id(row) if row != UNKNOWN_USER else "nobody"
    return json.dumps({"user": user, "num": num}).encode()


def request_bytes(host: str, port: int, payload: bytes) -> bytes:
    return (
        f"POST /queries.json HTTP/1.1\r\nHost: {host}:{port}\r\n"
        f"Content-Type: application/json\r\n"
        f"Content-Length: {len(payload)}\r\n\r\n"
    ).encode() + payload


class Connection:
    """One keep-alive HTTP/1.1 connection over a raw socket."""

    def __init__(self, host: str, port: int, timeout: float):
        self.addr, self.timeout = (host, port), timeout
        self.sock, self.buf = None, b""
        self.connect()

    def connect(self) -> None:
        self.close()
        self.sock = socket.create_connection(self.addr, timeout=self.timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.buf = b""

    def close(self) -> None:
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass
        self.sock = None

    def exchange(self, request: bytes) -> tuple[int, bytes]:
        """Send one request; returns ``(status, body)``. Raises ``OSError``."""
        self.sock.sendall(request)
        while b"\r\n\r\n" not in self.buf:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise OSError("server closed the connection")
            self.buf += chunk
        head, _, self.buf = self.buf.partition(b"\r\n\r\n")
        status = int(head.split(b" ", 2)[1])
        length = 0
        for line in head.split(b"\r\n")[1:]:
            if line[:15].lower() == b"content-length:":
                length = int(line[15:])
                break
        while len(self.buf) < length:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise OSError("truncated response body")
            self.buf += chunk
        body, self.buf = self.buf[:length], self.buf[length:]
        return status, body


def run_window(spec: dict, wait_for_go) -> dict:
    """Drive the window; returns per-request arrays and the kept bodies."""
    traffic, seconds, seed = spec["traffic"], spec["seconds"], spec["seed"]
    host, port = spec["host"], spec["port"]
    open_loop = traffic["loop"] == "open"
    if open_loop:
        due = arrivals(traffic, seconds, seed)
        workers = traffic["connections"]
    else:
        due = np.zeros(int(spec["closed_loop_budget"]))
        workers = traffic["clients"]
    rows = draw_users(traffic, spec["n_users"], due.size, seed)
    requests = [request_bytes(host, port, body_for(int(r), traffic["num"]))
                for r in rows]
    n = due.size
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    status = np.zeros(n, dtype=np.int64)
    bodies: list = [None] * n
    lock = threading.Lock()
    cursor = [0]
    conns = [Connection(host, port, spec["timeout_s"]) for _ in range(workers)]
    clock = time.perf_counter
    t0 = [0.0]
    gate = threading.Event()

    def worker(conn: Connection) -> None:
        gate.wait()
        while True:
            with lock:
                k = cursor[0]
                cursor[0] += 1
            if k >= n:
                return
            now = clock() - t0[0]
            if open_loop:
                if due[k] > now:
                    time.sleep(due[k] - now)
            elif now >= seconds:
                return
            else:
                due[k] = now
            sent[k] = clock() - t0[0]
            try:
                status[k], bodies[k] = conn.exchange(requests[k])
                done[k] = clock() - t0[0]
            except (OSError, ValueError):
                status[k] = -1
                try:
                    conn.connect()
                except OSError:
                    return

    threads = [threading.Thread(target=worker, args=(c,), daemon=True)
               for c in conns]
    for t in threads:
        t.start()
    wait_for_go()
    t0[0] = clock()
    gate.set()
    for t in threads:
        t.join(timeout=seconds + spec["timeout_s"] + 5)
    for c in conns:
        c.close()
    return {"due": due, "sent": sent, "done": done, "status": status,
            "rows": rows, "bodies": bodies}


def main(argv=None) -> int:
    with open((argv or sys.argv[1:])[0]) as f:
        spec = json.load(f)

    def wait_for_go() -> None:
        print("ready", flush=True)
        if sys.stdin.readline().strip() != "go":
            raise SystemExit("the driver went away")

    rec = run_window(spec, wait_for_go)
    # requests never taken (closed loop's spare budget) are not attempts
    taken = ~np.isnan(rec["sent"])
    out = {k: rec[k][taken].tolist() for k in ("due", "sent", "done", "status", "rows")}
    out["bodies"] = [b.decode("utf-8", "replace") if b is not None else None
                     for b, t in zip(rec["bodies"], taken) if t]
    with open(spec["out"], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
