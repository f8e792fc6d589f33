"""The load generator of `rpc_closed_loop`: a child process of its own,
so that it shares neither the daemon's interpreter lock nor the chip.
It imports nothing of jax or of the program.

    python rpc_client.py <socket> <queries.json> <callers> <out.json>

`callers` persistent connections to the daemon's unix socket, each
with one request in flight: caller i waits `start_s[i]`, then sends
queries i, i + callers, i + 2*callers, ... (wrapping round), the next
one `think_s[i][j]` seconds after the reply to the last has come
(queries.json holds `queries`, `think_s` and `start_s`).  It prints `ready` when all are connected, starts
on `go` and stops sending on `stop` (both on stdin), waits for the
replies in flight, and writes every sample
`[query, t_send, t_recv]` (CLOCK_MONOTONIC seconds, the clock the
harness reads too) with the reply's text.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time

DRAIN_S = 60.0      # a reply may come a minute late; it is late, not lost


async def main(sock: str, queries_path: str, callers: int,
               out_path: str) -> int:
    with open(queries_path, encoding="utf8") as f:
        plan = json.load(f)
    queries, think_s, start_s = plan["queries"], plan["think_s"], \
        plan["start_s"]
    loop = asyncio.get_running_loop()
    conns = [await asyncio.open_unix_connection(sock, limit=1 << 24)
             for _ in range(callers)]
    stop = asyncio.Event()
    samples, replies, errors = [], {}, []

    async def pause(seconds: float) -> None:
        """Sleeps, or returns at once when `stop` comes."""
        if seconds > 0:
            try:
                await asyncio.wait_for(stop.wait(), seconds)
            except asyncio.TimeoutError:
                pass

    async def caller(i: int, reader, writer) -> None:
        k, j = i, 0
        await pause(start_s[i])
        while not stop.is_set():
            qi = k % len(queries)
            k += callers
            q = queries[qi]
            req = json.dumps({"jsonrpc": "2.0", "id": qi,
                              "method": q["method"],
                              "params": q["params"]}).encode()
            t0 = time.monotonic()
            writer.write(req)
            try:
                raw = await reader.readuntil(b"\n\n")
            except (asyncio.IncompleteReadError, ConnectionError) as e:
                errors.append(f"caller {i}: {e!r}")
                return
            samples.append([qi, t0, time.monotonic()])
            replies[qi] = raw.decode()
            await pause(think_s[i][j % len(think_s[i])])
            j += 1

    print("ready", flush=True)
    line = await loop.run_in_executor(None, sys.stdin.readline)
    if line.strip() != "go":
        return 2
    tasks = [asyncio.create_task(caller(i, r, w))
             for i, (r, w) in enumerate(conns)]
    await loop.run_in_executor(None, sys.stdin.readline)      # "stop"
    stop.set()
    done, pending = await asyncio.wait(tasks, timeout=DRAIN_S)
    for t in pending:
        t.cancel()
    for _, w in conns:
        w.close()
    with open(out_path, "w", encoding="utf8") as f:
        json.dump({"samples": samples, "replies": replies,
                   "errors": errors, "never_answered": len(pending)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(main(sys.argv[1], sys.argv[2], int(sys.argv[3]),
                              sys.argv[4])))
