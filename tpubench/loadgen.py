"""Open-loop load generator: sends a schedule of requests to a dbserver.

Runs as a child process of the harness and never imports JAX, so the
chip stays with the server's process.  It speaks the server's framing
(a 4-byte big-endian length, then UTF-8 JSON) itself.

    python tpubench/loadgen.py --host H --port P --schedule S --out O

``S`` holds one ``{"due": seconds, "req": {...}}`` per line.  Each request
is sent at its due time on a free connection (a new one when all are
busy), whatever happened to earlier ones.  Its latency is taken from the
due time, so a stall also counts against the requests it delays.  The
generator prints ``start`` when the first request is due and ``end``
once every request has been answered or, a minute after the last due
time, given up; then it writes one JSON line per request to ``O``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import struct
import sys
import time

_HEADER = struct.Struct(">I")
GIVE_UP_S = 60.0


class Pool:
    def __init__(self, host: str, port: int):
        self.host, self.port = host, port
        self.idle = []
        self.all = []

    async def get(self):
        if self.idle:
            return self.idle.pop()
        conn = await asyncio.open_connection(self.host, self.port)
        self.all.append(conn)
        return conn

    def put(self, conn) -> None:
        self.idle.append(conn)

    async def close(self) -> None:
        for _, w in self.all:
            w.close()
        for _, w in self.all:
            try:
                await w.wait_closed()
            except OSError:
                pass


async def one(pool: Pool, t0: float, item: dict, rec: dict) -> None:
    await asyncio.sleep(max(0.0, t0 + item["due"] - time.perf_counter()))
    rec["late"] = time.perf_counter() - (t0 + item["due"])
    conn = await pool.get()
    reader, writer = conn
    payload = json.dumps(item["req"], separators=(",", ":")).encode()
    writer.write(_HEADER.pack(len(payload)) + payload)
    await writer.drain()
    (n,) = _HEADER.unpack(await reader.readexactly(_HEADER.size))
    resp = json.loads(await reader.readexactly(n))
    rec["lat"] = time.perf_counter() - (t0 + item["due"])
    pool.put(conn)
    rec["status"] = resp.get("status")
    rec["generation"] = resp.get("generation")
    rec["rows"] = resp.get("rows")
    rec["cache"] = resp.get("cache")
    rec["updated"] = resp.get("updated")


async def main_async(args) -> list:
    with open(args.schedule) as f:
        items = [json.loads(line) for line in f]
    pool = Pool(args.host, args.port)
    for _ in range(args.connections):
        pool.put(await pool.get())
    recs = [{"i": i, "late": None, "lat": None, "status": None}
            for i in range(len(items))]
    t0 = time.perf_counter() + 0.05
    await asyncio.sleep(max(0.0, t0 - time.perf_counter()))
    print("start", flush=True)
    tasks = [asyncio.ensure_future(one(pool, t0, it, r))
             for it, r in zip(items, recs)]
    last_due = max(it["due"] for it in items)
    done, pending = await asyncio.wait(
        tasks, timeout=max(0.0, t0 + last_due + GIVE_UP_S
                           - time.perf_counter()))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    print("end", flush=True)
    for t, r in zip(tasks, recs):
        if t in done and t.exception() is not None:
            r["error"] = repr(t.exception())
    await pool.close()
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--host", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--connections", type=int, default=8)
    args = ap.parse_args(argv)
    recs = asyncio.run(main_async(args))
    with open(args.out, "w") as f:
        for r in recs:
            f.write(json.dumps(r, separators=(",", ":")) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
