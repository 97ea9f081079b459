"""Set-up and the measured window of a serving cell.

The program under test is ``BatchedEngine`` behind ``EngineServer``
(``POST /generate`` over HTTP/SSE). The benchmark's own spans wrap the
engine's calls from the outside: ``step`` (one tick), and within it the
extend and decode calls, each recorded with the host clock and, for a
traced run, as a ``jax.profiler.TraceAnnotation`` on the profiler's
clock. The wrappers also note the work each call was given (token rows,
attention key pairs, logit rows), read from the engine's host-side slot
state before the call, so the MFU readers can count what the step needed.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import pathlib
import sys
import time
from typing import List, Optional

import jax

LOADCLIENT = pathlib.Path(__file__).resolve().parent / "loadclient.py"


@dataclasses.dataclass
class Span:
    name: str          # step | decode | extend
    t0: float          # host perf_counter seconds
    t1: float
    rows: int = 0      # token rows the call computed for live requests
    key_pairs: int = 0  # causal (query, key) pairs of those rows
    logit_rows: int = 0  # rows whose logits a request needed


class Spans:
    """The benchmark's spans around the engine's calls."""

    def __init__(self, eng):
        self.items: List[Span] = []
        step, dec, ext = eng.step, eng._run_decode, eng._run_extend

        def wrapped_step():
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                step()
            self.items.append(Span("step", t0, time.perf_counter()))

        def wrapped_decode(decoding):
            lens = [len(eng._live[s].prompt) + len(eng._live[s].output)
                    for s in decoding]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.decode"):
                dec(decoding)
            self.items.append(Span("decode", t0, time.perf_counter(),
                                   len(decoding), sum(lens), len(decoding)))

        def wrapped_extend(takes):
            rows = pairs = done = 0
            for slot, take in takes.items():
                off = int(eng._offsets[slot])
                rows += take
                pairs += take * off + take * (take + 1) // 2
                done += off + take == len(eng._live[slot].prompt)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.extend"):
                ext(takes)
            self.items.append(Span("extend", t0, time.perf_counter(),
                                   rows, pairs, done))

        eng.step = wrapped_step
        eng._run_decode = wrapped_decode
        eng._run_extend = wrapped_extend

    def between(self, t0: float, t1: float) -> List[Span]:
        return [s for s in self.items if s.t0 >= t0 and s.t1 <= t1]


def require_spans(spans: List[Span], records: list, seconds: float) -> None:
    """Fails the run when the window served tokens that no wrapped extend
    or decode call produced: the engine then ran its ticks by a path the
    wrappers do not see, and the readings built on the spans would be
    left out without a word."""
    ts = [[t for t in r["token_s"] if t < seconds] for r in records]
    seen = {s.name for s in spans}
    for name, served in (("extend", any(ts)),
                         ("decode", any(len(t) > 1 for t in ts))):
        if served and name not in seen:
            raise RuntimeError(
                f"the window served tokens but recorded no {name} span: "
                f"BatchedEngine no longer calls _run_{name} from step(), "
                f"so perfbench/harness/serving.py Spans must follow it")


class Compiles:
    """Counts backend compilations (to tell whether any ran inside the
    window)."""

    def __init__(self):
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache" or \
                "backend_compile" in event:
            self.times.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


async def warm_requests(port: int, prompts: List[List[int]],
                        max_tokens: int) -> None:
    """Send requests over the wire and wait for all of them: runs every
    host path of a request (admission, extend over several chunks,
    decode, retirement) before the window, so nothing first-use lands in
    it."""
    sys.path.insert(0, str(LOADCLIENT.parent))
    import loadclient

    recs = [{"tokens": [], "token_s": []} for _ in prompts]
    await asyncio.gather(*(
        loadclient._generate(port, {"prompt": p, "max_tokens": max_tokens,
                                    "temperature": 0.0}, rec, time.perf_counter)
        for p, rec in zip(prompts, recs)))
    for rec in recs:
        if rec.get("status") != 200 or len(rec["tokens"]) != max_tokens:
            raise RuntimeError(f"warm-up request failed: {rec}")


@dataclasses.dataclass
class Window:
    start: float                  # host perf_counter at the window's start
    close: float
    records: list
    stats0: dict
    stats1: dict
    trace_dir: Optional[str] = None
    trace_window: Optional[tuple] = None   # (t0, t1) host seconds


async def run_window(srv, schedule: dict, seconds: float, workdir: str,
                     trace_at: Optional[float] = None,
                     trace_len: float = 0.0) -> Window:
    """Start the load client, and with it the window; optionally trace
    ``trace_len`` seconds from ``trace_at`` seconds in; read the engine's
    counters at the start and at the close; return the client's records."""
    sched = pathlib.Path(workdir) / "schedule.json"
    out = pathlib.Path(workdir) / "records.json"
    sched.write_text(json.dumps(schedule))
    proc = await asyncio.create_subprocess_exec(
        sys.executable, str(LOADCLIENT), "--port", str(srv.port),
        "--schedule", str(sched), "--seconds", str(seconds), "--out",
        str(out), stdout=asyncio.subprocess.PIPE,
        stderr=asyncio.subprocess.PIPE)
    try:
        line = await proc.stdout.readline()
        if line.strip() != b"start":
            raise RuntimeError(f"load client did not start: {line!r}")
        start = time.perf_counter()
        stats0 = srv.engine.stats()
        tdir, twin = None, None
        if trace_at is not None:
            await asyncio.sleep(max(0.0, start + trace_at
                                    - time.perf_counter()))
            tdir = str(pathlib.Path(workdir) / "trace")
            loop = asyncio.get_running_loop()
            await loop.run_in_executor(None, jax.profiler.start_trace, tdir)
            t0 = time.perf_counter()
            await asyncio.sleep(trace_len)
            t1 = time.perf_counter()
            await loop.run_in_executor(None, jax.profiler.stop_trace)
            twin = (t0, t1)
        line = await proc.stdout.readline()
        close = time.perf_counter()
        stats1 = srv.engine.stats()
        _, err = await proc.communicate()
        if line.strip() != b"close":
            raise RuntimeError(f"load client did not close: {line!r}: "
                               f"{err.decode()[-2000:]}")
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"load client failed ({proc.returncode}): "
                           f"{err.decode()[-2000:]}")
    return Window(start, close, json.loads(out.read_text()), stats0, stats1,
                  tdir, twin)
