"""Open-loop HTTP load: one process, one thread, one asyncio loop.

Each request is sent when it is DUE, whether or not earlier ones have
answered, and is timed from when it was due — a stall then costs every
request it delays. How late the generator itself ran (sent minus due) is
recorded beside it, so a starved generator is not read as a fast server.
"""
from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass
from typing import List, Optional

import aiohttp

from benchmarks.lib.traffic import Request


@dataclass
class Outcome:
    request: Request
    due: float                    # time.monotonic() when it was due
    sent: float = 0.0
    done: float = 0.0             # last byte of the answer
    status: int = 0
    error: Optional[str] = None
    body: Optional[dict] = None   # unary answers only
    finished: bool = False        # unary: 200 + choices; SSE: [DONE] seen
    tokens: int = 0               # tokens the answer holds, once proved

    @property
    def ok(self) -> bool:
        return self.finished and self.error is None and self.status == 200


async def post(session: aiohttp.ClientSession, url: str, payload: dict,
               out: Outcome) -> Outcome:
    """One request. A streamed answer (``text/event-stream``) is read line
    by line to its ``[DONE]``; any other answer is read whole."""
    out.sent = time.monotonic()
    try:
        async with session.post(url, json=payload) as resp:
            out.status = resp.status
            if resp.headers.get("Content-Type", "").startswith(
                    "text/event-stream"):
                async for raw in resp.content:
                    line = raw.strip()
                    if line.startswith(b"event: error"):
                        out.error = "stream ended on an error event"
                    if line == b"data: [DONE]":
                        out.finished = True
            else:
                text = await resp.text()
                if resp.status == 200:
                    body = json.loads(text)
                    if "error" in body:
                        out.error = str(body["error"])[:200]
                    else:
                        out.body = body
                        out.finished = True
                else:
                    out.error = f"HTTP {resp.status}: {text[:200]}"
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
            json.JSONDecodeError) as e:
        out.error = f"{type(e).__name__}: {e}"[:200]
    out.done = time.monotonic()
    return out


def payload_for(req: Request, template: dict) -> dict:
    return {**template, "prompt": req.prompt, "max_tokens": req.max_tokens}


async def open_loop(session: aiohttp.ClientSession, url: str,
                    requests: List[Request], template: dict,
                    start: float) -> List["asyncio.Task[Outcome]"]:
    """Send ``requests`` on schedule (``start`` + ``due_s`` on
    ``time.monotonic()``); returns the in-flight tasks, one per request,
    as soon as the last one is SENT."""
    tasks = []
    for req in requests:
        due = start + req.due_s
        delay = due - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(
            post(session, url, payload_for(req, template),
                 Outcome(req, due))))
    return tasks


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (the q-th of n sorted values is a value
    that was measured, never an interpolation between two)."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of nothing")
    k = max(0, min(len(s) - 1, int(-(-q * len(s) // 100)) - 1))
    return s[k]
