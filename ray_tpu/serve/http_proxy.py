"""HTTP ingress proxy (reference: ``python/ray/serve/_private/proxy.py`` —
per-node ProxyActor routing HTTP to replicas via the router).

An aiohttp server inside an async actor. Routes come from the controller's
route table (longest-prefix match); request bodies pass to the ingress
deployment's ``__call__`` as a dict: ``{"body": bytes, "path": str,
"query": dict, "headers": dict, "method": str}`` — JSON responses are
serialized automatically.

Production semantics (reference: the proxy's request lifecycle):

- **Admission control**: a global in-flight cap (``rt_config.
  serve_max_inflight``) sheds excess load with 503 + ``Retry-After``
  before any routing work happens.
- **Deadlines**: per-request result deadline (``serve_request_timeout_s``)
  maps to 504 + ``Retry-After``; per-chunk stream deadline
  (``serve_stream_chunk_timeout_s``) bounds wedged streams.
- **Typed status mapping**: infra failures the client may retry
  (saturation, replica death mid-request) are 503 + ``Retry-After``;
  deadlines are 504; only APPLICATION errors are 500.
- **Streams fail loudly**: a mid-stream failure emits a terminal
  ``event: error`` SSE frame instead of silently truncating, and a client
  disconnect cancels the replica-side generator so its slot frees now.

A request's way through here is on record. The handler mints ``req`` (the
client's ``X-Request-Id`` where it sent one) and stamps ``received`` at its
first line; both ride with the call (``handle.options(origin=)``) to the
replica's ``serve.replica.call`` / ``serve.replica.pull`` and the
deployment's ``llm.request`` / ``llm.done``. At its end, whatever the
outcome, the handler leaves a ``serve.proxy.request`` span
(``jax.profiler.TraceAnnotation``, inert unless a capture runs in this
process; no span stays open across an ``await``), whose arguments are the
request's ledger (``Ledger``), and ``ProxyBase.stats()`` sums the same parts
with no capture.
"""
from __future__ import annotations

import asyncio
import json
import logging
import os
import time
import uuid
from typing import Dict, Optional, Tuple

logger = logging.getLogger(__name__)

_ROUTE_TTL_S = 1.0  # controller route-table cache horizon


def _classify_error(e: BaseException) -> str:
    """'retryable' | 'deadline' | 'app' — the ONE classification both
    ingresses map from (HTTP 503/504/500, gRPC UNAVAILABLE/
    DEADLINE_EXCEEDED/INTERNAL). Retryable infra classes and deadlines
    never surface as bare application errors."""
    from ray_tpu.exceptions import GetTimeoutError
    from ray_tpu.serve.handle import ServeRetryableError

    if isinstance(e, ServeRetryableError):
        return "retryable"
    if isinstance(e, (GetTimeoutError, TimeoutError, asyncio.TimeoutError)):
        return "deadline"
    return "app"


def _error_status(e: BaseException):
    """(status, retry_after) for an exception escaping a handle call."""
    return {
        "retryable": (503, "1"),
        "deadline": (504, "1"),
        "app": (500, None),
    }[_classify_error(e)]


class Ledger:
    """What one request spent where in the proxy: seconds, each part taken
    with ``time.monotonic()`` around the await it names, for the arguments
    of ``serve.proxy.request`` and the sums of ``ProxyBase.stats()``."""

    __slots__ = (
        "req", "received", "inflight", "status", "stream", "route_fetched",
        "route_s", "read_s", "submit_s", "register_s", "pulls",
        "pull_wait_s", "write_s", "after_last_pull_s", "bytes")

    def __init__(self, req: str, received: float, inflight: int):
        self.req, self.received, self.inflight = req, received, inflight
        # the status the client got; for a stream that failed after its
        # 200 went out, the one its error maps to (the client got that as
        # a terminal frame); 0 where the handler was cancelled
        self.status = 0
        self.stream = self.route_fetched = self.pulls = self.bytes = 0
        self.route_s = self.read_s = self.submit_s = self.register_s = 0.0
        self.pull_wait_s = self.write_s = self.after_last_pull_s = 0.0

    @property
    def origin(self) -> Tuple[str, float]:
        """What rides with the call to the replica."""
        return self.req, self.received

    async def timed(self, part: str, awaitable):
        """Await, and add the wait to ``part``."""
        began = time.monotonic()
        try:
            return await awaitable
        finally:
            setattr(self, part,
                    getattr(self, part) + time.monotonic() - began)


def _ms(seconds: float) -> float:
    return round(seconds * 1e3, 3)


class ProxyBase:
    """Ingress-agnostic half of a serve proxy: route resolution with a
    short cache, admission counters, the request's id and ledger, and
    stream teardown. Both the HTTP and gRPC proxies inherit it — the pieces
    live ONCE, with real `self` ownership of the state they touch (each
    proxy renders rejections in its own protocol)."""

    def __init__(self):
        # Admission control + observability counters (single event loop:
        # plain ints are race-free).
        self._inflight = 0
        self._handles: Dict[str, object] = {}
        self._routes_cache = (-10.0, {})
        self._stats = dict.fromkeys(
            ("requests", "streams", "shed", "errors", "route_fetches",
             "pulls", "bytes"), 0)
        self._stats.update(dict.fromkeys(
            ("route_s", "submit_s", "register_s", "pull_wait_s", "write_s",
             "total_s"), 0.0))

    def stats(self) -> dict:
        """The proxy's ``engine.stats``: ``inflight`` now, and since the
        start the sums of what each closed request's ``serve.proxy.request``
        says of it (``_close``): ``requests``, of them ``streams``, ``shed``
        (the in-flight cap's 503s) and ``errors`` (every other answer of 500
        and above), ``route_fetches`` (requests that went to the controller
        for the route table) and the seconds and counts of the ledger's
        parts. Its readers: an operator (PERF.md section 3), and tier-1,
        which holds each sum against the spans' arguments
        (``tests/test_serve_path_spans.py``). The gRPC proxy closes no
        ledger yet: of these it counts ``shed`` alone."""
        return {"inflight": self._inflight, "pid": os.getpid(),
                **self._stats}

    def _over_cap(self) -> bool:
        """Admission check: True when the request must be shed (counts
        the shed); the caller renders the 503 / RESOURCE_EXHAUSTED."""
        from ray_tpu._private.config import rt_config

        cap = int(rt_config.serve_max_inflight)
        if cap > 0 and self._inflight >= cap:
            self._stats["shed"] += 1
            return True
        return False

    def _open(self, client_id: Optional[str] = None) -> Ledger:
        """A request enters: its id (the client's, or a short hex one
        minted here) and the stamp of its arrival."""
        return Ledger(client_id or uuid.uuid4().hex[:16], time.monotonic(),
                      self._inflight)

    def _close(self, led: Ledger, shed: bool = False) -> None:
        """A request leaves, whatever its outcome: its ledger goes into the
        sums and onto a ``serve.proxy.request`` span."""
        from ray_tpu.util.debug import span

        total = time.monotonic() - led.received
        sums = self._stats
        sums["requests"] += 1
        sums["streams"] += led.stream
        sums["errors"] += led.status >= 500 and not shed
        sums["route_fetches"] += led.route_fetched
        sums["pulls"] += led.pulls
        sums["bytes"] += led.bytes
        sums["total_s"] += total
        for part in ("route_s", "submit_s", "register_s", "pull_wait_s",
                     "write_s"):
            sums[part] += getattr(led, part)

        with span(
            "serve.proxy.request", req=led.req, status=led.status,
            stream=led.stream, inflight=led.inflight,
            route_ms=_ms(led.route_s), route_fetched=led.route_fetched,
            read_ms=_ms(led.read_s), submit_ms=_ms(led.submit_s),
            register_ms=_ms(led.register_s), pulls=led.pulls,
            pull_wait_ms=_ms(led.pull_wait_s), write_ms=_ms(led.write_s),
            after_last_pull_ms=_ms(led.after_last_pull_s), bytes=led.bytes,
            total_ms=_ms(total),
        ):
            pass

    def _route_for(self, path: str) -> Tuple[Optional[str], int]:
        """(the deployment behind ``path`` or None, 1 where this call went
        to the controller for the route table and 0 where the cached one
        answered)."""
        import ray_tpu
        from ray_tpu._private import faultpoints
        from ray_tpu.serve.controller import CONTROLLER_NAME

        if faultpoints.ACTIVE:
            faultpoints.fire("serve.proxy.route", err=ConnectionError)

        fetched = 0

        def fetch():
            nonlocal fetched
            fetched = 1
            routes = ray_tpu.get(
                ray_tpu.get_actor(CONTROLLER_NAME).get_routes.remote(),
                timeout=10,
            )
            self._routes_cache = (time.monotonic(), routes)
            return routes

        def match(routes):
            best = None
            for prefix, deployment in routes.items():
                if path.startswith(prefix) and (
                    best is None or len(prefix) > len(best[0])
                ):
                    best = (prefix, deployment)
            return None if best is None else best[1]

        fetched_at, routes = self._routes_cache
        fresh = time.monotonic() - fetched_at <= _ROUTE_TTL_S
        if not fresh:
            routes = fetch()
        found = match(routes)
        if found is None and fresh:
            # Miss on a warm cache: a route registered moments ago must
            # not 404 for the cache TTL — refetch once before giving up.
            found = match(fetch())
        return found, fetched

    def _close_stream(self, it):
        """Release the handle-side stream iterator (settles the router
        slot and cancels the replica generator); safe on non-stream
        iterators and None."""
        close = getattr(it, "close", None)
        if close is not None:
            try:
                close()
            except Exception as e:
                logger.debug("stream close raised: %s", e)


class HTTPProxy(ProxyBase):
    def __init__(self, host: str = "127.0.0.1", port: int = 8000):
        super().__init__()
        self._host = host
        self._port = port
        self._runner = None
        self._site = None

    async def start(self) -> int:
        from aiohttp import web

        app = web.Application()
        app.router.add_route("*", "/{tail:.*}", self._handle)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        self._site = web.TCPSite(self._runner, self._host, self._port)
        await self._site.start()
        port = self._site._server.sockets[0].getsockname()[1]
        self._port = port
        return port

    def port(self) -> int:
        return self._port

    async def _handle(self, request):
        from aiohttp import web
        from ray_tpu._private.config import rt_config

        led = self._open(request.headers.get("X-Request-Id"))
        # Admission control: shed BEFORE any routing work. Saturation must
        # degrade to fast typed rejections, not queue collapse.
        if self._over_cap():
            led.status = 503
            self._close(led, shed=True)
            return web.Response(
                status=503,
                text=f"proxy saturated: {self._inflight} requests in "
                     f"flight >= serve_max_inflight="
                     f"{int(rt_config.serve_max_inflight)}",
                headers={"Retry-After": "1"},
            )
        self._inflight += 1
        try:
            resp = await self._handle_admitted(request, led)
            led.status = led.status or resp.status
            return resp
        except Exception:
            led.status = 500  # what aiohttp answers where the handler raises
            raise
        finally:
            self._inflight -= 1
            self._close(led)

    async def _handle_admitted(self, request, led: Ledger):
        from aiohttp import web

        loop = asyncio.get_running_loop()
        try:
            # The controller RPC blocks; keep it off the proxy event loop.
            deployment, led.route_fetched = await led.timed(
                "route_s", loop.run_in_executor(
                    None, self._route_for, request.path))
        except Exception as e:
            # Route resolution is infra, not the app: a controller blip or
            # injected fault is a retryable 503, never a bare 500.
            return web.Response(
                status=503, text=f"route resolution failed: {e}",
                headers={"Retry-After": "1"},
            )
        if deployment is None:
            return web.Response(status=404, text="no route")
        from ray_tpu.serve.handle import DeploymentHandle

        handle = self._handles.get(deployment)
        if handle is None:
            handle = self._handles[deployment] = DeploymentHandle(deployment)
        began = time.monotonic()
        body = await request.read()
        payload = {
            "body": body,
            "path": request.path,
            "query": dict(request.query),
            "headers": dict(request.headers),
            "method": request.method,
        }
        # SSE streaming: a JSON body with "stream": true rides the serve
        # streaming protocol (replica-side generator) and is forwarded as
        # text/event-stream chunks (reference: Serve HTTP streaming
        # responses / OpenAI stream=true).
        wants_stream = False
        try:
            parsed = json.loads(body or b"{}")
            wants_stream = bool(
                isinstance(parsed, dict) and parsed.get("stream")
            )
        except json.JSONDecodeError:
            pass
        led.read_s = time.monotonic() - began
        led.stream = int(wants_stream)
        if wants_stream:
            return await self._handle_stream(
                request, handle.options(stream=True, origin=led.origin),
                payload, loop, led,
            )
        from ray_tpu._private.config import rt_config

        timeout = float(rt_config.serve_request_timeout_s)
        handle = handle.options(origin=led.origin)
        try:
            # Submission may briefly block (router pick / controller
            # refresh): keep it off the loop. The WAIT is fully async —
            # parking a blocked executor thread per in-flight request
            # starves co-located replicas (all actors in a worker process
            # share one default executor) and deadlocks under bursts.
            resp = await led.timed("submit_s", loop.run_in_executor(
                None, lambda: handle.remote(payload)
            ))
            out = await led.timed("register_s", resp.result_async(timeout))
        except Exception as e:
            return self._error_response(e)
        return self._plain_response(out, led)

    def _error_response(self, e: BaseException):
        from aiohttp import web

        status, retry_after = _error_status(e)
        headers = {"Retry-After": retry_after} if retry_after else None
        return web.Response(
            status=status, text=f"{type(e).__name__}: {e}",
            headers=headers,
        )

    def _plain_response(self, out, led: Ledger):
        """A whole answer as one response, which aiohttp sends after the
        handler returned: its bytes are on the ledger, its write is not."""
        from aiohttp import web

        if isinstance(out, (bytes, bytearray)):
            resp = web.Response(body=bytes(out))
        elif isinstance(out, str):
            resp = web.Response(text=out)
        else:
            resp = web.json_response(out)
        led.bytes = len(resp.body or b"")
        return resp

    async def _handle_stream(self, request, handle, payload, loop,
                             led: Ledger):
        from aiohttp import web
        from ray_tpu._private.config import rt_config

        from ray_tpu.serve.handle import _StreamIterator

        done = object()  # stream-exhausted sentinel
        # wait_for horizon sits ABOVE the handle's own per-chunk pull
        # deadline so the typed handle-side error wins over a raw timeout.
        chunk_timeout = float(rt_config.serve_stream_chunk_timeout_s) + 30

        async def _next():
            # __anext__ applies the handle-side per-chunk deadline and
            # maps replica death to the typed retryable class; the outer
            # wait_for is the backstop if the pull itself wedges.
            began = time.monotonic()
            try:
                return await asyncio.wait_for(it.__anext__(), chunk_timeout)
            except StopAsyncIteration:
                return done
            finally:
                led.pull_wait_s += time.monotonic() - began
                led.pulls = it.pulls

        it = None
        try:
            # Submission off-loop (may briefly block on the router); the
            # stream registration wait and every chunk pull are async —
            # an open stream costs a coroutine, not a blocked executor
            # thread (co-located replicas share the executor).
            gen = await led.timed("submit_s", loop.run_in_executor(
                None, lambda: handle.remote(payload)
            ))
            # Registration (time-to-first-response) is bounded by the
            # REQUEST deadline like the unary path; only chunk pulls get
            # the longer streaming horizon.
            out = await led.timed("register_s", gen.result_async(
                float(rt_config.serve_request_timeout_s)
            ))
            if not isinstance(out, _StreamIterator):
                # The deployment chose not to stream (e.g. stream=true
                # with options the endpoint serves non-incrementally): a
                # plain response comes back shaped like the unary path,
                # not a broken SSE body.
                return self._plain_response(out, led)
            it = out
            first = await _next()
        except Exception as e:
            self._close_stream(it)
            return self._error_response(e)
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        await led.timed("write_s", resp.prepare(request))
        chunk = first
        try:
            while chunk is not done:
                if isinstance(chunk, str):
                    chunk = chunk.encode()
                elif not isinstance(chunk, (bytes, bytearray)):
                    # generic generator deployments may yield objects:
                    # frame them as JSON lines rather than dropping them
                    chunk = (json.dumps(chunk) + "\n").encode()
                began = time.monotonic()
                await resp.write(chunk)
                led.write_s += time.monotonic() - began
                led.bytes += len(chunk)
                chunk = await _next()
        except (ConnectionResetError, ConnectionError) as e:
            # CLIENT went away mid-stream: cancel the replica-side
            # generator so its slot frees now, not at the idle sweep.
            logger.debug("client left stream %s: %s", request.path, e)
        except Exception as e:
            # Mid-stream upstream failure: a silent truncation is
            # indistinguishable from success — emit a terminal typed
            # error event so the client KNOWS (and knows whether to
            # retry), then end the stream.
            logger.warning("stream to %s ended on error: %s: %s",
                           request.path, type(e).__name__, e)
            from ray_tpu.serve.handle import ServeRetryableError

            led.status = _error_status(e)[0]
            frame = {
                "error": type(e).__name__,
                "message": str(e),
                "retryable": isinstance(
                    e, (ServeRetryableError, TimeoutError,
                        asyncio.TimeoutError)
                ),
            }
            try:
                await resp.write(
                    b"event: error\ndata: "
                    + json.dumps(frame).encode() + b"\n\n"
                )
            except Exception as we:
                logger.debug("terminal error frame not delivered: %s", we)
        finally:
            self._close_stream(it)
        try:
            await led.timed("write_s", resp.write_eof())
        except Exception as e:
            logger.debug("eof after disconnect: %s", e)
        if it.drained_at:
            # the return of the pull that said done -> the last byte
            # handed to the socket
            led.after_last_pull_s = time.monotonic() - it.drained_at
        return resp

    async def stop(self) -> bool:
        if self._runner is not None:
            await self._runner.cleanup()
        return True
