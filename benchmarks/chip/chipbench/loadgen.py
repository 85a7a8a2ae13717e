"""Load generator: the clients of a run, in a process of their own.

    python loadgen.py < plan.json

Imports the standard library and ``traffic.py`` beside it (numpy;
never JAX: the chip belongs to the server's process), and takes every
timestamp on its own clock, so a server that stalls its event loop
cannot hide the stall from the client's numbers.  The plan on standard
input::

    {"host": "127.0.0.1", "port": 8008, "warmup_s": 10, "window_s": 30,
     "drain_s": 60, "traffic": {<the traffic file>}, "seed": 7,
     "vocab": 32000}

The requests are ``traffic.requests(traffic, seed, vocab, window_s)``,
drawn as they are sent.  Open loop: request ``i`` is due at ``at``
seconds after the start; it is sent then, late if the generator fell
behind.  Closed loop: ``clients`` clients each send their next request
when the last one ends, and never run out.  Requests are sent from the
start until the window closes (``warmup_s + window_s``); then the
requests in flight get ``drain_s`` seconds to end.

Standard output carries ``WINDOW_START <unix time>`` and ``WINDOW_END
<unix time>`` as the window opens and closes, then one line ``RESULT
<json>`` with a record per request sent: its due and send times, the
arrival time and id of each token event (seconds from the start), its
terminal event and the engine's request id.  The SSE client follows
``launch/server.py``'s ``sse_client``.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from typing import Dict, List

import traffic


async def _stream(host: str, port: int, spec: dict, rec: dict,
                  clock) -> None:
    """POST one request and record its events into ``rec``."""
    reader, writer = await asyncio.open_connection(host, port)
    body = json.dumps(spec).encode()
    writer.write((f"POST /generate HTTP/1.1\r\n"
                  f"Host: {host}\r\nContent-Type: application/json\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    rec["sent"] = clock()
    try:
        await writer.drain()
        status = int((await reader.readline()).decode().split(" ", 2)[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if status != 200:
            rec["terminal"] = f"http_{status}"
            return
        event, data = "message", {}
        while True:
            line = await reader.readline()
            if not line:
                rec["terminal"] = rec["terminal"] or "disconnected"
                return
            text = line.decode().rstrip("\r\n")
            if text.startswith("event:"):
                event = text[6:].strip()
            elif text.startswith("data:"):
                data = json.loads(text[5:].strip())
            elif text == "":
                if event == "token":
                    rec["times"].append(clock())
                    rec["tokens"].append(int(data["token"]))
                else:
                    rec["terminal"] = event
                    rec["req_id"] = data.get("req_id")
                    rec["error"] = data.get("error")
                    return
                event, data = "message", {}
    finally:
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionResetError, BrokenPipeError):
            pass


def _record(i: int, due: float) -> dict:
    return {"i": i, "due": due, "sent": None, "times": [], "tokens": [],
            "terminal": None, "req_id": None, "error": None}


async def run(plan: dict, out=sys.stdout) -> dict:
    """Drive the plan; return the result written after ``RESULT``."""
    host, port = plan["host"], int(plan["port"])
    mix = plan["traffic"]
    reqs = enumerate(traffic.requests(mix, int(plan["seed"]),
                                      int(plan["vocab"]),
                                      float(plan["window_s"])))
    t0 = time.perf_counter()

    def clock() -> float:
        return time.perf_counter() - t0

    w_start = float(plan["warmup_s"])
    w_end = w_start + float(plan["window_s"])
    records: List[dict] = []
    tasks: Dict[int, asyncio.Task] = {}

    async def one(i: int, due: float, r: traffic.Request) -> None:
        rec = _record(i, due)
        records.append(rec)
        try:
            await _stream(host, port, {"prompt": r.prompt,
                                       "max_new_tokens": r.max_new_tokens},
                          rec, clock)
        except (ConnectionError, OSError, ValueError,
                asyncio.IncompleteReadError) as e:
            rec["terminal"] = "client_error"
            rec["error"] = repr(e)

    async def marks() -> None:
        await asyncio.sleep(w_start - clock())
        out.write(f"WINDOW_START {time.time()!r}\n")
        out.flush()
        await asyncio.sleep(w_end - clock())
        out.write(f"WINDOW_END {time.time()!r}\n")
        out.flush()

    async def open_loop() -> None:
        for i, r in reqs:
            if r.at >= w_end:
                break
            delay = r.at - clock()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks[i] = asyncio.ensure_future(one(i, r.at, r))

    async def client() -> None:
        while clock() < w_end:
            i, r = next(reqs)
            task = asyncio.ensure_future(one(i, clock(), r))
            tasks[i] = task
            # at the window's end the client stops; its request in
            # flight is left to the drain below
            await asyncio.wait([task], timeout=max(0.0, w_end - clock()))
            if not task.done():
                return

    mark_task = asyncio.ensure_future(marks())
    if mix["loop"] == "open":
        senders = [asyncio.ensure_future(open_loop())]
    else:
        senders = [asyncio.ensure_future(client())
                   for _ in range(int(mix["clients"]))]
    await asyncio.gather(mark_task, *senders)
    # every client stopped sending at the window's end; the requests in
    # flight get drain_s to finish, and a request that never ends is
    # recorded as such
    pending = [t for t in tasks.values() if not t.done()]
    if pending:
        _, late = await asyncio.wait(pending, timeout=float(plan["drain_s"]))
        for t in late:
            t.cancel()
        await asyncio.gather(*late, return_exceptions=True)
    for rec in records:
        if rec["terminal"] is None:
            rec["terminal"] = "never_finished"
    records.sort(key=lambda r: r["i"])
    return {"window": [w_start, w_end], "end": clock(),
            "records": records}


def main() -> None:
    plan = json.load(sys.stdin)
    result = asyncio.run(run(plan))
    sys.stdout.write("RESULT " + json.dumps(result) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
