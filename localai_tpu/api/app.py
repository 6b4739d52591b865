"""HTTP application assembly.

Parity with the reference's fiber app (reference: core/http/app.go:52-188 —
error handler, request logging, metrics middleware, bearer key-auth on
everything with GET exemptions, CORS, route registration), re-based on
aiohttp. Blocking capability calls run on a thread pool; token streams
bridge into asyncio via a queue.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import math
import re
import secrets
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional

from aiohttp import web

from localai_tpu.services.errors import ServingError
from localai_tpu.services.metrics import METRICS

log = logging.getLogger("localai_tpu.api")

# GET paths reachable without an API key (reference: auth.go exemption list)
AUTH_EXEMPT = [
    re.compile(r"^/$"),
    re.compile(r"^/healthz$"),
    re.compile(r"^/readyz$"),
    re.compile(r"^/metrics$"),
    re.compile(r"^/static/"),
    re.compile(r"^/swagger"),
]


@web.middleware
async def error_middleware(request, handler):
    try:
        return await handler(request)
    except web.HTTPException:
        raise
    except ServingError as e:
        # structured lifecycle failures (shed / backend down / circuit
        # open / deadline): the right status + Retry-After, one WARNING —
        # a full traceback for an expected overload would drown the logs
        log.warning("serving error: %s %s -> %d %s: %s", request.method,
                    request.path, e.status, e.etype, e)
        return error_response(e)
    except Exception as e:
        log.exception("handler error: %s %s", request.method, request.path)
        return api_error(str(e), 500)


def api_error(message: str, status: int = 500, etype: str = "server_error"):
    """OpenAI-style error envelope (reference: schema.ErrorResponse)."""
    return web.json_response(
        {"error": {"message": message, "type": etype, "param": None, "code": status}},
        status=status,
    )


def error_response(e: ServingError) -> web.Response:
    """ServingError -> OpenAI-style envelope with its HTTP status, the
    breaker/retryability detail merged into the error object, and a
    Retry-After header when the engine provided a hint."""
    body = {"message": str(e), "type": e.etype, "param": None,
            "code": e.status}
    body.update(e.body_extra())
    headers = {}
    if e.retry_after_s:
        headers["Retry-After"] = str(math.ceil(e.retry_after_s))
    if e.status == 429:
        METRICS.inc("http_requests_shed_total")
    return web.json_response({"error": body}, status=e.status,
                             headers=headers)


# observability surfaces excluded from per-request http spans: scrapes
# and debug pulls would otherwise fill the frontend ring with their own
# reads of it
_TRACE_SKIP = re.compile(r"^/(metrics|debug/|healthz|readyz|static/)")


def make_metrics_middleware():
    import uuid

    from localai_tpu.services.tracing import frontend_tracer

    @web.middleware
    async def metrics_middleware(request, handler):
        t0 = time.perf_counter()
        # ONE trace context per request (ISSUE 12): minted here (or taken
        # from X-Correlation-ID), read by every route via
        # request["correlation_id"], propagated to the backend over
        # localai-trace-id invocation metadata — both processes' spans
        # share this id on the merged /debug/trace timeline.
        rid = request.headers.get("X-Correlation-ID") or uuid.uuid4().hex
        request["correlation_id"] = rid
        t_mono = time.monotonic()
        status = [0]
        try:
            resp = await handler(request)
            status[0] = resp.status
            return resp
        finally:
            # label by the matched route PATTERN, not the raw path —
            # raw paths (job uuids, 404 probes) are unbounded-cardinality
            resource = request.match_info.route.resource
            path = resource.canonical if resource else "unmatched"
            METRICS.observe_api_call(request.method, path,
                                     time.perf_counter() - t0)
            tr = frontend_tracer()
            if tr.enabled and not _TRACE_SKIP.match(request.path):
                tr.record("http", "http", t_mono, time.monotonic(),
                          rid=rid, args={"method": request.method,
                                         "path": path,
                                         "status": status[0] or 500})
    return metrics_middleware


def make_auth_middleware(api_keys: list):
    @web.middleware
    async def auth_middleware(request, handler):
        if not api_keys:
            return await handler(request)
        if request.method in ("GET", "OPTIONS") and any(
            p.match(request.path) for p in AUTH_EXEMPT
        ):
            return await handler(request)
        auth = request.headers.get("Authorization", "")
        key = auth.removeprefix("Bearer ").strip()
        if key and any(secrets.compare_digest(key, k) for k in api_keys):
            return await handler(request)
        return api_error("invalid api key", 401, "invalid_request_error")
    return auth_middleware


def make_cors_middleware(allow_origins: str = "*"):
    @web.middleware
    async def cors_middleware(request, handler):
        if request.method == "OPTIONS":
            resp = web.Response(status=204)
        else:
            resp = await handler(request)
        resp.headers["Access-Control-Allow-Origin"] = allow_origins
        resp.headers["Access-Control-Allow-Headers"] = "Authorization, Content-Type"
        resp.headers["Access-Control-Allow-Methods"] = "GET, POST, DELETE, OPTIONS"
        return resp
    return cors_middleware


EXECUTOR_WORKERS = 256


class AppState:
    """Shared server state hung off the aiohttp app."""

    def __init__(self, caps, app_config, gallery_service=None):
        self.caps = caps
        self.config = app_config
        self.gallery_service = gallery_service
        # one worker a streamed request for its whole life: as many as the
        # runner's gRPC pool gives a model of many slots
        # (backend/runner.py::RPC_WORKERS_MANY_SLOTS), so that the engine's
        # queue, and not this pool's, is where a request waits (threads
        # start on demand)
        self.executor = ThreadPoolExecutor(max_workers=EXECUTOR_WORKERS,
                                           thread_name_prefix="cap")
        self.started_at = time.time()

    async def run_blocking(self, fn, *args, **kwargs):
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(self.executor, lambda: fn(*args, **kwargs))

    async def iter_blocking(self, gen_factory) -> "asyncio.Queue":
        """Run a sync generator on the pool; yield items via an async queue.

        Never blocks the pump thread (unbounded queue + put_nowait), so a
        client disconnect cannot wedge an executor worker; the consumer sets
        q.cancel_event to stop the generator early (GeneratorExit runs its
        finally blocks, releasing busy marks / backend streams).
        """
        loop = asyncio.get_running_loop()
        q: asyncio.Queue = asyncio.Queue()
        SENTINEL = object()
        cancel = threading.Event()

        def pump():
            gen = gen_factory()
            try:
                for item in gen:
                    if cancel.is_set():
                        break
                    loop.call_soon_threadsafe(q.put_nowait, item)
            except Exception as e:
                loop.call_soon_threadsafe(q.put_nowait, e)
            finally:
                try:
                    gen.close()
                except Exception:
                    log.exception("stream generator close failed")
                loop.call_soon_threadsafe(q.put_nowait, SENTINEL)

        self.executor.submit(pump)
        q.sentinel = SENTINEL  # type: ignore[attr-defined]
        q.cancel_event = cancel  # type: ignore[attr-defined]
        return q


def get_state(request) -> AppState:
    return request.app["state"]


async def sse_response(request, chunks: "asyncio.Queue"):
    """Drain an async queue of dicts into an SSE stream, ending with [DONE]
    (reference: chat.go:463-508 fasthttp StreamWriter)."""
    # peek the FIRST item before committing to a 200 + event-stream: a
    # request shed by admission control or refused by an open circuit
    # fails before any token is produced, and the client deserves a real
    # 429/503 with Retry-After — not a 200 stream containing an error
    first = await chunks.get()
    if isinstance(first, ServingError):
        if hasattr(chunks, "cancel_event"):
            chunks.cancel_event.set()
        log.warning("stream refused: %s %s -> %d %s: %s", request.method,
                    request.path, first.status, first.etype, first)
        return error_response(first)
    resp = web.StreamResponse(headers={
        "Content-Type": "text/event-stream",
        "Cache-Control": "no-cache",
        "Connection": "keep-alive",
        "X-Accel-Buffering": "no",
    })
    await resp.prepare(request)
    seed: list = [first]
    try:
        done = False
        while not done:
            # greedy drain: one socket write per batch of queued chunks.
            # A decode burst delivers many tokens at once, and per-token
            # write+flush is the dominant host cost of the SSE path on a
            # 1-core rig (VERDICT r4 #2)
            batch = seed or [await chunks.get()]
            seed = []
            while True:
                try:
                    batch.append(chunks.get_nowait())
                except asyncio.QueueEmpty:
                    break
            out = bytearray()
            for item in batch:
                if item is chunks.sentinel:
                    done = True
                    break
                if isinstance(item, Exception):
                    # mid-stream failure: the 200 is already on the wire,
                    # so the typed error rides the stream body instead
                    err = {"message": str(item), "type": "server_error"}
                    if isinstance(item, ServingError):
                        err["type"] = item.etype
                        err.update(item.body_extra())
                    out += f"data: {json.dumps({'error': err})}\n\n".encode()
                    done = True
                    break
                if isinstance(item, (bytes, bytearray)):
                    out += item   # pre-framed by the route (already "data: ...\n\n")
                else:
                    out += f"data: {json.dumps(item, ensure_ascii=False)}\n\n".encode()
            if out:
                await resp.write(bytes(out))
        await resp.write(b"data: [DONE]\n\n")
    except (ConnectionResetError, asyncio.CancelledError):
        raise
    finally:
        if hasattr(chunks, "cancel_event"):
            chunks.cancel_event.set()
        with contextlib.suppress(OSError, ConnectionResetError):
            await resp.write_eof()
    return resp


def build_app(caps, app_config, gallery_service=None) -> web.Application:
    from localai_tpu.api import localai_routes, openai_routes

    state = AppState(caps, app_config, gallery_service)
    middlewares = [error_middleware, make_metrics_middleware()]
    if app_config.cors:
        middlewares.append(make_cors_middleware(app_config.cors_allow_origins))
    middlewares.append(make_auth_middleware(app_config.api_keys))
    app = web.Application(
        middlewares=middlewares,
        client_max_size=app_config.upload_limit_mb * 1024 * 1024,
    )
    app["state"] = state
    openai_routes.register(app)
    localai_routes.register(app)

    from localai_tpu.api import assistants_routes

    assistants_routes.register(app)
    if not app_config.disable_webui:
        from localai_tpu.api import webui

        webui.register(app)

    from localai_tpu.api import swagger

    swagger.register(app)
    return app


async def run_app(app, address: str):
    host, _, port = address.rpartition(":")
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, host or "0.0.0.0", int(port))
    await site.start()
    return runner
