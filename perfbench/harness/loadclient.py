"""Load client: replays one schedule over the server's HTTP/SSE wire.

Runs as a child process that imports nothing but the standard library,
so it never competes with the engine's thread for the parent's
interpreter lock:

    python perfbench/harness/loadclient.py --port P --schedule S.json \
        --seconds 30 --out R.json

The window opens when the client starts (it prints ``start`` on stdout)
and closes ``--seconds`` later (it prints ``close``). Open-loop requests
are sent at their due times; a backlog keeps ``outstanding`` requests in
flight, sending the next as each one ends. Every time is taken from the
due time, not the send time, so a late client shows as latency and its
lateness is reported apart. At the close every request still in flight
is cut (the server aborts it), and one record per request goes to
``--out``: its due, send, token and end times (seconds from the window's
start; a backlog request never sent has no due time), its token ids and
whether the server finished it.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time


def _request_bytes(body: dict) -> bytes:
    data = json.dumps(body).encode()
    head = (f"POST /generate HTTP/1.1\r\nHost: localhost\r\n"
            f"Content-Length: {len(data)}\r\nConnection: close\r\n\r\n")
    return head.encode() + data


async def _read_head(reader):
    status = int((await reader.readline()).split()[1])
    headers = {}
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            return status, headers
        k, _, v = h.decode("latin1").partition(":")
        headers[k.strip().lower()] = v.strip()


async def _generate(port: int, body: dict, rec: dict, clock):
    """POST /generate and read the SSE stream into ``rec`` as it comes,
    so a request cut at the close keeps the tokens it already got."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        writer.write(_request_bytes(body))
        await writer.drain()
        status, headers = await _read_head(reader)
        rec["status"] = status
        if status != 200:
            rec["error"] = (await reader.read()).decode(errors="replace")
            return
        buf = b""
        while True:
            chunk = await reader.read(65536)
            if not chunk:
                return
            buf += chunk
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                if not frame.startswith(b"data: "):
                    continue
                evt = json.loads(frame[6:])
                now = clock()
                if "token" in evt:
                    rec["tokens"].append(int(evt["token"]))
                    rec["token_s"].append(now)
                elif evt.get("done"):
                    rec["finished"] = evt.get("finish_reason") == "length"
                    rec["end_s"] = now
                    return
                elif "error" in evt:
                    rec["error"] = str(evt["error"])
                    return
    finally:
        writer.close()


async def run(port: int, schedule: dict, seconds: float) -> list:
    t0 = time.perf_counter()

    def clock():
        return time.perf_counter() - t0

    print("start", flush=True)
    reqs = schedule["requests"]
    backlog = schedule["mode"] != "open"
    recs = [{"i": i, "due_s": None if backlog else r["due_s"],
             "send_s": None, "status": None,
             "tokens": [], "token_s": [], "end_s": None, "finished": False,
             "error": None} for i, r in enumerate(reqs)]
    tasks = []

    async def one(i: int):
        rec = recs[i]
        delay = rec["due_s"] - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        rec["send_s"] = clock()
        body = {"prompt": reqs[i]["prompt"],
                "max_tokens": reqs[i]["max_tokens"], "temperature": 0.0}
        try:
            await _generate(port, body, rec, clock)
        except (ConnectionError, OSError) as e:
            rec["error"] = f"{type(e).__name__}: {e}"

    if not backlog:
        tasks = [asyncio.ensure_future(one(i)) for i in range(len(reqs))]
        await asyncio.sleep(max(0.0, seconds - clock()))
    else:
        # backlog: ``outstanding`` in flight, the next sent as one ends;
        # its due time is when it was sent (it was always waiting)
        nxt = 0
        live = set()
        while clock() < seconds and (live or nxt < len(reqs)):
            while len(live) < schedule["outstanding"] and nxt < len(reqs):
                recs[nxt]["due_s"] = clock()
                t = asyncio.ensure_future(one(nxt))
                tasks.append(t)
                live.add(t)
                nxt += 1
            done, live = await asyncio.wait(
                live, timeout=max(0.0, seconds - clock()),
                return_when=asyncio.FIRST_COMPLETED)
        if nxt >= len(reqs) and clock() < seconds:
            raise RuntimeError("the backlog ran dry inside the window")
    print("close", flush=True)
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return recs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    with open(args.schedule) as f:
        schedule = json.load(f)
    recs = asyncio.run(run(args.port, schedule, args.seconds))
    with open(args.out, "w") as f:
        json.dump(recs, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
