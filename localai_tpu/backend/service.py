"""gRPC plumbing for the backend contract — hand-rolled stubs.

The environment has grpcio + protoc but not grpcio-tools, so instead of
generated service stubs this module builds client/server bindings from a
method table using grpc's generic API. Same wire format, less magic.

Parity: reference pkg/grpc/client.go (Go client, one method per RPC) and
pkg/grpc/server.go (shim letting in-tree backends serve the proto). The
reference dials a new connection per call (client.go:60 — noted as a wart
in SURVEY.md); here one channel is created per backend and reused.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent import futures
from typing import Iterator, Optional

import grpc

from localai_tpu.backend import contract_pb2 as pb
from localai_tpu.services.faults import FAULTS

_log = logging.getLogger("localai_tpu.backend.service")

SERVICE = "localai_tpu.Backend"

# name -> (request message, response message, server_streaming)
METHODS = {
    "Health": (pb.HealthMessage, pb.Reply, False),
    "LoadModel": (pb.ModelOptions, pb.Result, False),
    "Predict": (pb.PredictOptions, pb.Reply, False),
    "PredictStream": (pb.PredictOptions, pb.Reply, True),
    "Embedding": (pb.PredictOptions, pb.EmbeddingResult, False),
    "TokenizeString": (pb.PredictOptions, pb.TokenizationResponse, False),
    "GenerateImage": (pb.GenerateImageRequest, pb.Result, False),
    "TTS": (pb.TTSRequest, pb.Result, False),
    "SoundGeneration": (pb.SoundGenerationRequest, pb.Result, False),
    "AudioTranscription": (pb.TranscriptRequest, pb.TranscriptResult, False),
    "Rerank": (pb.RerankRequest, pb.RerankResult, False),
    "Status": (pb.HealthMessage, pb.StatusResponse, False),
    "GetMetrics": (pb.MetricsRequest, pb.MetricsResponse, False),
    # observability side-channel (no new proto messages — the hand-rolled
    # stubs can't grow fields, but METHODS can grow RPCs):
    #   GetTrace: Reply.message carries Chrome trace-event JSON (UTF-8)
    #   Profile:  PredictOptions.prompt carries a JSON {"seconds": N};
    #             Result.message is the capture directory
    #   GetState: Reply.message carries a JSON {"state": engine state
    #             snapshot, "events": event-log ring} (ISSUE 8)
    "GetTrace": (pb.MetricsRequest, pb.Reply, False),
    "GetState": (pb.MetricsRequest, pb.Reply, False),
    "Profile": (pb.PredictOptions, pb.Result, False),
    "StoresSet": (pb.StoresSetOptions, pb.Result, False),
    "StoresDelete": (pb.StoresDeleteOptions, pb.Result, False),
    "StoresGet": (pb.StoresGetOptions, pb.StoresGetResult, False),
    "StoresFind": (pb.StoresFindOptions, pb.StoresFindResult, False),
}


def parse_options(options: str) -> dict:
    """ModelOptions.options wire format ("k=v,k2=v2", produced by
    capabilities.build_model_options) -> dict. The ONE parser every
    backend shares."""
    return dict(kv.split("=", 1) for kv in (options or "").split(",")
                if "=" in kv)


class BackendServicer:
    """Base servicer: every RPC answers UNIMPLEMENTED unless overridden.

    Concrete backends (engine runner, fake echo, store backend) override
    the subset they support — mirrors the reference's base backend
    (pkg/grpc/base/base.go:16 'Unimplemented' pattern).
    """

    def Health(self, request, context) -> pb.Reply:
        # the reply carries this process's wall clock (epoch ms) in the
        # Reply's spare double: a front end on another machine estimates
        # the clock offset from a few of these round trips
        # (modelmgr/loader.py::measure_clock)
        return pb.Reply(message=b"OK",
                        timing_prompt_processing=time.time() * 1e3)

    def __getattr__(self, name):
        if name in METHODS:
            def _unimplemented(request, context):
                context.abort(grpc.StatusCode.UNIMPLEMENTED, f"{name} not implemented")
            return _unimplemented
        raise AttributeError(name)


def _inject_faults(name: str, fn, streaming: bool):
    """Wrap an RPC handler with the chaos-harness injection points
    (services/faults.py). With nothing armed this is one attribute read
    per call. Wrapping at the server layer covers every backend — the
    real engine runner AND the fake echo backend tests spawn.

    - ``rpc_unavailable=<Method>``: abort that RPC with UNAVAILABLE
      before the handler runs (the client-side idempotent-unary retry
      must absorb it).
    - ``kill_backend_after_tokens=N``: hard-exit the backend process
      after N streamed PredictStream tokens (a mid-stream crash, the
      supervisor's worst case).
    """
    if streaming:
        def wrapped(request, context):
            if FAULTS.active and FAULTS.take("rpc_unavailable", match=name):
                context.abort(grpc.StatusCode.UNAVAILABLE,
                              f"injected fault: rpc_unavailable on {name}")
            tokens = 0
            for resp in fn(request, context):
                yield resp
                if FAULTS.active:
                    tokens += len(getattr(resp, "token_ids", ()) or ()) or 1
                    kill = FAULTS.value("kill_backend_after_tokens")
                    if kill is not None and tokens >= int(kill):
                        FAULTS.take("kill_backend_after_tokens")
                        _log.warning(
                            "injected fault: killing backend after %d "
                            "streamed tokens", tokens)
                        import os

                        os._exit(17)
    else:
        def wrapped(request, context):
            if FAULTS.active and FAULTS.take("rpc_unavailable", match=name):
                context.abort(grpc.StatusCode.UNAVAILABLE,
                              f"injected fault: rpc_unavailable on {name}")
            return fn(request, context)
    return wrapped


class RpcPool(futures.ThreadPoolExecutor):
    """A server's handler threads, whose number a servicer may raise once
    it knows what it serves (a streamed request holds a thread for its
    whole life). ``grow`` never lowers it."""

    def grow(self, max_workers: int):
        # ThreadPoolExecutor reads _max_workers at every submit and starts
        # a thread while it has fewer (tests/test_granite_hybrid.py holds
        # CPython to that)
        self._max_workers = max(self._max_workers, max_workers)


def make_server(servicer: BackendServicer, addr: str, max_workers: int = 16,
                options: Optional[list] = None,
                pool: Optional[futures.Executor] = None) -> grpc.Server:
    """Build (not start) a grpc server for the contract bound to addr;
    ``pool`` in place of a fixed pool of ``max_workers`` threads."""
    handlers = {}
    for name, (req_cls, resp_cls, streaming) in METHODS.items():
        fn = _inject_faults(name, getattr(servicer, name), streaming)
        if streaming:
            h = grpc.unary_stream_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=resp_cls.SerializeToString)
        else:
            h = grpc.unary_unary_rpc_method_handler(
                fn, request_deserializer=req_cls.FromString,
                response_serializer=resp_cls.SerializeToString)
        handlers[name] = h
    server = grpc.server(
        pool or futures.ThreadPoolExecutor(max_workers=max_workers),
        options=options or [
            ("grpc.max_receive_message_length", 64 * 1024 * 1024),
            ("grpc.max_send_message_length", 64 * 1024 * 1024),
        ],
    )
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler(SERVICE, handlers),)
    )
    # add_insecure_port returns 0 on bind failure WITHOUT raising; an
    # unchecked 0 surfaces later as an opaque connect timeout. Raising
    # here makes the free_port() -> bind race a deterministic message the
    # spawn-side retry (modelmgr/process.py) can detect in the log tail.
    if server.add_insecure_port(addr) == 0:
        raise RuntimeError(f"could not bind {addr}: address already in use")
    return server


class BackendClient:
    """Typed client over one reusable channel.

    `parallel=False` serializes Predict* calls with a lock, matching the
    reference's opMutex behavior for backends that cannot batch
    (pkg/grpc/client.go:15-22).
    """

    def __init__(self, addr: str, parallel: bool = True):
        self.addr = addr
        self.parallel = parallel
        self._lock = threading.Lock()
        self._channel = grpc.insecure_channel(
            addr,
            options=[
                ("grpc.max_receive_message_length", 64 * 1024 * 1024),
                ("grpc.max_send_message_length", 64 * 1024 * 1024),
            ],
        )
        self._stubs = {}
        for name, (req_cls, resp_cls, streaming) in METHODS.items():
            path = f"/{SERVICE}/{name}"
            if streaming:
                self._stubs[name] = self._channel.unary_stream(
                    path, request_serializer=req_cls.SerializeToString,
                    response_deserializer=resp_cls.FromString)
            else:
                self._stubs[name] = self._channel.unary_unary(
                    path, request_serializer=req_cls.SerializeToString,
                    response_deserializer=resp_cls.FromString)

    def close(self):
        self._channel.close()

    def _maybe_locked(self):
        class _NullCtx:
            def __enter__(self): return None
            def __exit__(self, *a): return False
        return self._lock if not self.parallel else _NullCtx()

    def _retry_unary(self, name: str, req, timeout: float,
                     attempts: int = 3, base_delay: float = 0.05):
        """Call an IDEMPOTENT unary RPC, retrying on UNAVAILABLE with
        exponential delay (ISSUE 7 crash recovery): a one-packet blip or
        a backend mid-respawn should cost a retry, not a client error.
        Only read-only/stateless methods route through here — Predict*
        may have produced tokens before dying and must never re-run
        implicitly."""
        delay = base_delay
        for attempt in range(attempts):
            try:
                return self._stubs[name](req, timeout=timeout)
            except grpc.RpcError as e:
                code = e.code() if callable(getattr(e, "code", None)) else None
                if code != grpc.StatusCode.UNAVAILABLE \
                        or attempt == attempts - 1:
                    raise
                _log.warning("%s UNAVAILABLE (attempt %d/%d), retrying in "
                             "%.2fs", name, attempt + 1, attempts, delay)
                time.sleep(delay)
                delay *= 2

    # --- typed wrappers ---
    def health(self, timeout: float = 5.0) -> bool:
        # wait_for_ready rides out gRPC's reconnect backoff while a spawned
        # backend is still importing — without it, fail-fast probes and the
        # backoff schedule can interleave so health never observes readiness.
        try:
            r = self._stubs["Health"](pb.HealthMessage(), timeout=timeout,
                                      wait_for_ready=True)
            return r.message == b"OK"
        except grpc.RpcError:
            return False

    def health_clock(self, timeout: float = 5.0) -> float:
        """One Health round trip -> the backend's wall clock (epoch s) as
        stamped in the reply, 0.0 from a backend that does not stamp."""
        r = self._stubs["Health"](pb.HealthMessage(), timeout=timeout)
        return r.timing_prompt_processing / 1e3

    def load_model(self, opts: pb.ModelOptions, timeout: float = 900.0) -> pb.Result:
        return self._stubs["LoadModel"](opts, timeout=timeout)

    def predict(self, opts: pb.PredictOptions, timeout: float = 600.0,
                metadata=None) -> pb.Reply:
        # per-request scheduling hints (e.g. ("localai-priority", "high"))
        # ride invocation metadata: the compiled descriptor cannot grow
        # PredictOptions fields (ISSUE 10)
        with self._maybe_locked():
            return self._stubs["Predict"](opts, timeout=timeout,
                                          metadata=metadata)

    def predict_stream(self, opts: pb.PredictOptions, timeout: float = 600.0,
                       metadata=None) -> Iterator[pb.Reply]:
        with self._maybe_locked():
            yield from self._stubs["PredictStream"](opts, timeout=timeout,
                                                    metadata=metadata)

    def embedding(self, opts: pb.PredictOptions, timeout: float = 120.0) -> pb.EmbeddingResult:
        return self._retry_unary("Embedding", opts, timeout)

    def tokenize(self, opts: pb.PredictOptions, timeout: float = 60.0) -> pb.TokenizationResponse:
        return self._retry_unary("TokenizeString", opts, timeout)

    def generate_image(self, req: pb.GenerateImageRequest, timeout: float = 600.0) -> pb.Result:
        return self._stubs["GenerateImage"](req, timeout=timeout)

    def tts(self, req: pb.TTSRequest, timeout: float = 600.0) -> pb.Result:
        return self._stubs["TTS"](req, timeout=timeout)

    def sound_generation(self, req: pb.SoundGenerationRequest, timeout: float = 600.0) -> pb.Result:
        return self._stubs["SoundGeneration"](req, timeout=timeout)

    def transcribe(self, req: pb.TranscriptRequest, timeout: float = 600.0) -> pb.TranscriptResult:
        return self._stubs["AudioTranscription"](req, timeout=timeout)

    def rerank(self, req: pb.RerankRequest, timeout: float = 120.0) -> pb.RerankResult:
        return self._retry_unary("Rerank", req, timeout)

    def status(self, timeout: float = 10.0) -> pb.StatusResponse:
        return self._stubs["Status"](pb.HealthMessage(), timeout=timeout)

    def get_metrics(self, timeout: float = 10.0) -> pb.MetricsResponse:
        return self._retry_unary("GetMetrics", pb.MetricsRequest(),
                                 timeout)

    def get_trace(self, timeout: float = 10.0) -> pb.Reply:
        """Chrome trace-event JSON of the engine's span ring (UTF-8 in
        Reply.message)."""
        return self._stubs["GetTrace"](pb.MetricsRequest(), timeout=timeout)

    def get_state(self, timeout: float = 10.0) -> pb.Reply:
        """Live engine-state + event-log ring snapshot (JSON in
        Reply.message, ISSUE 8). Read-only — safe to retry."""
        return self._retry_unary("GetState", pb.MetricsRequest(), timeout)

    def profile(self, seconds: float, timeout: float = 120.0) -> pb.Result:
        """Capture a jax.profiler trace for `seconds`; Result.message is
        the directory holding the capture."""
        import json

        opts = pb.PredictOptions(prompt=json.dumps({"seconds": seconds}))
        return self._stubs["Profile"](opts, timeout=timeout)

    def stores_set(self, req: pb.StoresSetOptions, timeout: float = 60.0) -> pb.Result:
        return self._stubs["StoresSet"](req, timeout=timeout)

    def stores_delete(self, req: pb.StoresDeleteOptions, timeout: float = 60.0) -> pb.Result:
        return self._stubs["StoresDelete"](req, timeout=timeout)

    def stores_get(self, req: pb.StoresGetOptions, timeout: float = 60.0) -> pb.StoresGetResult:
        return self._stubs["StoresGet"](req, timeout=timeout)

    def stores_find(self, req: pb.StoresFindOptions, timeout: float = 60.0) -> pb.StoresFindResult:
        return self._stubs["StoresFind"](req, timeout=timeout)
