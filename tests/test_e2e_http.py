"""Real-engine HTTP e2e: backend/runner.py subprocess behind the full app.

The reference's integration tier boots the whole server against real
models and drives it over HTTP (reference: core/http/app_test.go:263-344).
This module is that tier for the TPU build: a tiny random-weights llama
checkpoint is served by a spawned backend/runner.py process (real
tokenizer, real engine, real gRPC), and requests flow
HTTP -> capabilities -> gRPC -> engine -> SSE with no fakes anywhere.
"""

import asyncio
import json
import os
import threading

import httpx
import pytest

from localai_tpu.api.app import build_app, run_app
from localai_tpu.capabilities import Capabilities
from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.model_config import scan_models_dir
from localai_tpu.modelmgr.loader import ModelLoader
from localai_tpu.modelmgr.process import free_port

from tests.tinymodel import write_tiny_checkpoint

pytestmark = pytest.mark.e2e

TINY_YAML = """\
name: tiny
backend: tpu-llm
parameters:
  model: tiny-ckpt
  temperature: 0.7
  seed: 42
  max_tokens: 12
context_size: 128
num_slots: 4
dtype: float32
prefill_buckets: [16, 64]
template:
  completion: "{{ Input }}"
  chat_message: "{{ Role }}: {{ Content }}"
  chat: "{{ Input }}\\nassistant:"
"""


class Handle:
    def __init__(self, base, loader):
        self.base = base
        self.loader = loader


@pytest.fixture(scope="module")
def real_server(tmp_path_factory):
    models = tmp_path_factory.mktemp("models")
    write_tiny_checkpoint(str(models / "tiny-ckpt"))
    (models / "tiny.yaml").write_text(TINY_YAML)

    port = free_port()
    app_config = AppConfig(models_path=str(models), address=f"127.0.0.1:{port}")
    loader = ModelLoader(health_attempts=600, health_interval_s=0.2)
    configs = scan_models_dir(str(models))
    assert "tiny" in configs
    caps = Capabilities(app_config, loader, configs)
    app = build_app(caps, app_config)

    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run():
        asyncio.set_event_loop(loop)

        async def boot():
            await run_app(app, app_config.address)
            started.set()

        loop.run_until_complete(boot())
        loop.run_forever()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(10)
    yield Handle(f"http://127.0.0.1:{port}", loader)
    loop.call_soon_threadsafe(loop.stop)
    loader.stop_all()


# generous timeouts: the first request spawns the backend process and
# compiles prefill + decode (CPU XLA, single core)
FIRST = 600.0
WARM = 120.0


def test_chat_stream_through_real_engine(real_server):
    with httpx.stream("POST", f"{real_server.base}/v1/chat/completions", json={
        "model": "tiny", "stream": True, "max_tokens": 12, "ignore_eos": True,
        "messages": [{"role": "user", "content": "hello engine"}],
    }, timeout=FIRST) as r:
        assert r.status_code == 200, r.read()
        assert r.headers["content-type"].startswith("text/event-stream")
        events = []
        for line in r.iter_lines():
            if line.startswith("data: "):
                events.append(line[len("data: "):])
    assert events[-1] == "[DONE]"
    chunks = [json.loads(e) for e in events[:-1]]
    assert chunks[0]["choices"][0]["delta"]["role"] == "assistant"
    # ignore_eos + max_tokens=12 must finish with "length" and exactly 12
    # completion tokens — would catch both a broken prefill and a wrong
    # finish_reason in the final SSE chunk
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert chunks[-1]["usage"]["completion_tokens"] == 12
    assert chunks[-1]["usage"]["prompt_tokens"] > 0


def test_completions_nonstream(real_server):
    r = httpx.post(f"{real_server.base}/v1/completions", json={
        "model": "tiny", "prompt": "abc", "max_tokens": 8, "ignore_eos": True,
    }, timeout=WARM)
    assert r.status_code == 200, r.text
    body = r.json()
    ch = body["choices"][0]
    assert ch["finish_reason"] == "length"
    assert body["usage"]["completion_tokens"] == 8
    assert body["usage"]["prompt_tokens"] >= 3


def test_completions_deterministic_with_seed(real_server):
    def once():
        r = httpx.post(f"{real_server.base}/v1/completions", json={
            "model": "tiny", "prompt": "determinism", "max_tokens": 8,
            "ignore_eos": True, "seed": 7,
        }, timeout=WARM)
        assert r.status_code == 200, r.text
        return r.json()["choices"][0]["text"]

    assert once() == once()


def test_tokenize_real_tokenizer(real_server):
    r = httpx.post(f"{real_server.base}/v1/tokenize", json={
        "model": "tiny", "content": "hello world",
    }, timeout=WARM)
    assert r.status_code == 200, r.text
    toks = r.json()["tokens"]
    # byte-level tokenizer: one token per byte
    assert len(toks) == len("hello world")


def test_stop_sequence_through_engine(real_server):
    r = httpx.post(f"{real_server.base}/v1/completions", json={
        "model": "tiny", "prompt": "xyz", "max_tokens": 32, "ignore_eos": True,
        "seed": 3,
    }, timeout=WARM)
    assert r.status_code == 200
    full = r.json()["choices"][0]["text"]
    assert len(full) > 0
    # pick a substring the model actually emits and use it as a stop seq
    stop = full[2:4]
    if stop:
        r2 = httpx.post(f"{real_server.base}/v1/completions", json={
            "model": "tiny", "prompt": "xyz", "max_tokens": 32,
            "ignore_eos": True, "seed": 3, "stop": [stop],
        }, timeout=WARM)
        body = r2.json()["choices"][0]
        assert stop not in body["text"]
        assert body["finish_reason"] == "stop"


TOOLS = [{
    "type": "function",
    "function": {
        "name": "get_weather",
        "parameters": {
            "type": "object",
            "properties": {"city": {"enum": ["sf", "nyc"]}},
            "required": ["city"],
        },
    },
}]


def test_tool_call_forced_by_grammar(real_server):
    """Random weights + tools => grammar-masked decoding must yield a
    syntactically valid tool call (the reference's flagship constrained-
    decoding behavior, grpc-server.cpp:688,1977)."""
    r = httpx.post(f"{real_server.base}/v1/chat/completions", json={
        "model": "tiny", "max_tokens": 96, "temperature": 1.0, "seed": 11,
        "messages": [{"role": "user", "content": "weather in sf?"}],
        "tools": TOOLS, "tool_choice": "required",
    }, timeout=FIRST)
    assert r.status_code == 200, r.text
    choice = r.json()["choices"][0]
    assert choice["finish_reason"] == "tool_calls"
    calls = choice["message"]["tool_calls"]
    assert calls[0]["function"]["name"] == "get_weather"
    args = json.loads(calls[0]["function"]["arguments"])
    assert args["city"] in ("sf", "nyc")


def test_tool_call_streaming(real_server):
    with httpx.stream("POST", f"{real_server.base}/v1/chat/completions", json={
        "model": "tiny", "stream": True, "max_tokens": 96, "temperature": 1.0,
        "seed": 13,
        "messages": [{"role": "user", "content": "weather please"}],
        "tools": TOOLS, "tool_choice": "required",
    }, timeout=FIRST) as r:
        assert r.status_code == 200
        events = [json.loads(l[6:]) for l in r.iter_lines()
                  if l.startswith("data: ") and l != "data: [DONE]"]
    tool_chunks = [e for e in events
                   if e["choices"][0]["delta"].get("tool_calls")]
    assert tool_chunks, f"no tool_calls delta in stream: {events}"
    call = tool_chunks[0]["choices"][0]["delta"]["tool_calls"][0]
    assert call["function"]["name"] == "get_weather"
    assert json.loads(call["function"]["arguments"])["city"] in ("sf", "nyc")
    assert events[-1]["choices"][0]["finish_reason"] == "tool_calls"


def test_concurrent_requests_share_slots(real_server):
    import concurrent.futures

    def one(seed):
        r = httpx.post(f"{real_server.base}/v1/completions", json={
            "model": "tiny", "prompt": f"req {seed}", "max_tokens": 8,
            "ignore_eos": True, "seed": seed,
        }, timeout=WARM)
        assert r.status_code == 200, r.text
        return r.json()["usage"]["completion_tokens"]

    with concurrent.futures.ThreadPoolExecutor(max_workers=3) as ex:
        counts = list(ex.map(one, [1, 2, 3]))
    assert counts == [8, 8, 8]
