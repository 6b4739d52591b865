"""The load generator: one process, one thread, an asyncio loop.

It sends the schedule's chat requests to /v1/chat/completions with SSE
streaming, greedy, ``ignore_eos`` (so every output has its drawn length),
and times each from the client's side on ``time.monotonic()``. An open
loop sends on the schedule whatever the server does, and times a request
from when it was *due*; a closed loop sends a client's next request when
its last completes. Nothing here knows a metric: it returns records.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import List, Optional

import aiohttp

from benchmark.server import MODEL
from benchmark.traffic_lib import Request, Schedule, text_of

REQUEST_TIMEOUT_S = 120.0
DRAIN_TIMEOUT_S = 90.0


@dataclasses.dataclass
class Record:
    req: Request
    in_window: bool
    due: float = 0.0                  # absolute, monotonic
    sent: float = 0.0
    first: Optional[float] = None     # first content delta
    last: Optional[float] = None      # last content delta
    done: Optional[float] = None
    prompt_tokens: int = -1           # as the server counted them
    completion_tokens: int = -1
    error: str = ""

    @property
    def ok(self) -> bool:
        return (not self.error and self.first is not None
                and self.completion_tokens == self.req.max_tokens
                and self.prompt_tokens == self.req.prompt_tokens)


@dataclasses.dataclass
class Run:
    w0: float                          # window start, monotonic
    w1: float
    w0_wall: float
    records: List[Record]              # every request sent, warm-up included
    token_events: List[tuple]          # (monotonic time, tokens in the delta)


async def _one(session, base, rec: Record, token_events):
    body = {"model": MODEL, "stream": True, "temperature": 0.0,
            "ignore_eos": True, "max_tokens": rec.req.max_tokens,
            "messages": [{"role": "user",
                          "content": text_of(rec.req.prompt_ids)}]}
    rec.sent = time.monotonic()
    try:
        async with session.post(base + "/v1/chat/completions", json=body
                                ) as resp:
            if resp.status != 200:
                rec.error = f"HTTP {resp.status}: {(await resp.text())[:300]}"
                return
            async for raw in resp.content:
                if not raw.startswith(b"data:") or raw.startswith(b"data: [DONE]"):
                    continue
                now = time.monotonic()
                ev = json.loads(raw[5:])
                if ev.get("error"):
                    rec.error = str(ev["error"])[:300]
                    continue
                usage = ev.get("usage")
                if usage:
                    rec.prompt_tokens = usage["prompt_tokens"]
                    rec.completion_tokens = usage["completion_tokens"]
                text = (ev["choices"][0].get("delta") or {}).get("content")
                if text:
                    if rec.first is None:
                        rec.first = now
                    rec.last = now
                    token_events.append((now, len(text.split())))
    except asyncio.CancelledError:
        rec.error = rec.error or "cancelled"
        raise
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError,
            KeyError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:300]
    finally:
        rec.done = time.monotonic()


async def _open_loop(session, base, sched: Schedule, seconds, records,
                     token_events, on_window):
    t_start = time.monotonic() + 0.05
    w0 = t_start + sched.warmup_s
    pending = sorted(
        [Record(r, False, due=w0 + r.due) for r in sched.warmup]
        + [Record(r, True, due=w0 + r.due) for r in sched.window],
        key=lambda rec: rec.due)
    tasks = []
    side = asyncio.ensure_future(on_window(w0, w0 + seconds))
    for rec in pending:
        delay = rec.due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        records.append(rec)
        tasks.append(asyncio.ensure_future(
            _one(session, base, rec, token_events)))
    _, late = await asyncio.wait(tasks, timeout=seconds + DRAIN_TIMEOUT_S)
    for t in late:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    await side
    return w0


async def _closed_loop(session, base, sched: Schedule, seconds, records,
                       token_events, on_window):
    t_start = time.monotonic() + 0.05
    w0 = t_start + sched.warmup_s
    w1 = w0 + seconds
    nxt = 0

    async def client():
        nonlocal nxt
        while time.monotonic() < w1:
            req = sched.window[nxt % len(sched.window)]
            nxt += 1
            now = time.monotonic()
            rec = Record(req, w0 <= now < w1, due=now)
            records.append(rec)
            await _one(session, base, rec, token_events)

    side = asyncio.ensure_future(on_window(w0, w1))
    tasks = [asyncio.ensure_future(client()) for _ in range(sched.clients)]
    await asyncio.sleep(max(0.0, w1 - time.monotonic()))
    # the window is over: what is still in flight decides no metric
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    await side
    return w0


async def _no_side(w0, w1):
    return None


async def drive(base: str, sched: Schedule, seconds: float,
                on_window=None) -> Run:
    """Send the schedule. ``on_window(w0, w1)`` is a coroutine started with
    the traffic; a traced run uses it to profile and to sample state."""
    records, token_events = [], []
    timeout = aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S)
    conn = aiohttp.TCPConnector(limit=0)
    loop_fn = _open_loop if sched.mode == "open" else _closed_loop
    async with aiohttp.ClientSession(timeout=timeout, connector=conn
                                     ) as session:
        w0 = await loop_fn(session, base, sched, seconds, records,
                           token_events, on_window or _no_side)
    wall_minus_mono = time.time() - time.monotonic()
    return Run(w0, w0 + seconds, w0 + wall_minus_mono, records, token_events)


async def first_request(base: str) -> float:
    """The request that makes the model manager load the model; returns
    its wall time (LoadModel plus one tiny completion)."""
    rec = Record(Request([5, 6, 7, 8, 9, 10, 11, 12], 4, None), False)
    timeout = aiohttp.ClientTimeout(total=1100)
    async with aiohttp.ClientSession(timeout=timeout) as session:
        await _one(session, base, rec, [])
    if not rec.ok:
        raise RuntimeError(f"the loading request failed: {rec.error or rec}")
    return rec.done - rec.sent
