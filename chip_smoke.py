#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

drives the serving main path once, end to end, at the full width of a
Llama-3.1-8B (hidden 4096, 32 layers, 32 heads over 8 KV heads of 128,
FFN 14336, vocab 128256; int8 weights, bf16 paged KV, 16 slots x 1024):

  1. `python -m localai_tpu.ops.pallas.parity` — every Pallas kernel,
     both KV dtypes, compiled and checked against its jnp reference on
     the chip. The process exits, which releases the chip.
  2. `python -m localai_tpu run --models-path <dir> --address ...` — the
     documented server, which spawns the runner through the model
     manager exactly as a user's does. The model directory (config.json
     only, weights seeded random through LOCALAI_ALLOW_RANDOM_WEIGHTS=1,
     a word-level tokenizer, the YAML) is generated here.
  3. A handful of /v1/chat/completions requests chosen so that every
     program class of the engine's tick runs: non-streamed and SSE,
     greedy and sampled, a prompt longer than prefill_chunk (a
     `continued` pack), the same prompt again (a prefix-cache splice), a
     penalised request (plain decode bursts; everything else rides the
     speculative tick), and a wave twice as wide as the slots.
  4. The backend's own report (/debug/state, /metrics): the device it
     holds, per-device memory, compiles after the warm mark, respawns,
     kernel fallbacks, and which attention implementation each program
     was built with.

This process never imports jax: a chip belongs to one process, and it
belongs to the runner. Any failed phase raises, so the exit code is
non-zero and the result line is not printed; nothing is caught and
summarised under exit 0. The last line of stdout on success is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the runner's jax reports it. Finding no TPU is a
failure. Two flags the driver never passes:

  --tp N            serve on an N-chip mesh (`mesh: {tp: N}`), and check
                    that the weights are split N ways across the devices
  --cpu-rehearsal   a toy-width model on the CPU, to debug THIS script
                    before spending chip time; the result is stamped
                    "platform": "cpu" and "rehearsal": true
"""

import argparse
import json
import os
import random
import re
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 21
DEADLINE_S = 1150          # the contract allows 1200, compilation included
NAME = "smoke"
SLOTS, CONTEXT, CHUNK = 16, 1024, 512   # CHUNK: the engine's prefill_chunk

LLAMA_8B = dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                num_hidden_layers=32, num_attention_heads=32,
                num_key_value_heads=8, head_dim=128)
TOY = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
           num_hidden_layers=2, num_attention_heads=8,
           num_key_value_heads=4, head_dim=16)

T0 = time.monotonic()


def log(msg):
    print(f"[{time.monotonic() - T0:7.1f}s] {msg}", flush=True)


def remaining():
    left = DEADLINE_S - (time.monotonic() - T0)
    if left <= 0:
        raise TimeoutError(f"chip_smoke exceeded its {DEADLINE_S}s budget")
    return left


# ---------------------------------------------------------------- model dir

def write_model(root, shape, tp):
    """config.json-only checkpoint + word-level tokenizer + model YAML."""
    ckpt = os.path.join(root, NAME)
    os.makedirs(ckpt)
    with open(os.path.join(ckpt, "config.json"), "w") as f:
        json.dump({"architectures": ["LlamaForCausalLM"], **shape,
                   "max_position_embeddings": 2048, "rms_norm_eps": 1e-5,
                   "rope_theta": 500000.0, "bos_token_id": 1,
                   "eos_token_id": 2, "tie_word_embeddings": False}, f)
    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    vocab.update({f"t{i}": i for i in range(3, shape["vocab_size"])})
    with open(os.path.join(ckpt, "tokenizer.json"), "w") as f:
        json.dump({
            "version": "1.0", "truncation": None, "padding": None,
            "added_tokens": [
                {"id": i, "content": c, "single_word": False,
                 "lstrip": False, "rstrip": False, "normalized": False,
                 "special": True}
                for i, c in enumerate(("<unk>", "<s>", "</s>"))],
            "normalizer": None,
            "pre_tokenizer": {"type": "WhitespaceSplit"},
            "post_processor": None, "decoder": None,
            "model": {"type": "WordLevel", "vocab": vocab,
                      "unk_token": "<unk>"}}, f)
    with open(os.path.join(ckpt, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "bos_token": "<s>", "eos_token": "</s>",
                   "model_max_length": 2048}, f)
    # kv_cache_dtype is left at the engine default (bf16): the only dtype
    # that reaches the bf16 paged-decode and ragged-prefill kernels. The
    # ladder is narrowed with fields that exist for it (one prefill
    # bucket, burst 8) so a cold boot fits the time limit.
    with open(os.path.join(root, f"{NAME}.yaml"), "w") as f:
        f.write(f"""\
name: {NAME}
backend: tpu-llm
parameters:
  model: {NAME}
context_size: {CONTEXT}
num_slots: {SLOTS}
dtype: bfloat16
quantization: int8
decode_burst: 8
prefill_buckets: [{CHUNK}]
{f"mesh: {{tp: {tp}}}" if tp > 1 else "# mesh: one chip"}
template:
  completion: "{{{{ Input }}}}"
  chat_message: "{{{{ Content }}}}"
  chat: "{{{{ Input }}}}"
""")


def prompt(rng, n_tokens, vocab):
    return " ".join(f"t{rng.randrange(3, vocab)}" for _ in range(n_tokens))


# ---------------------------------------------------------------- processes

def _proc_stat(pid):
    """(state, ppid) of a process, or None when it is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return fields[0], int(fields[1])
    except (OSError, ValueError, IndexError):
        return None


def descendants(pid):
    """Every process below ``pid``. Backends run in their own sessions,
    so killing the server's process group does not reach them."""
    kids = {}
    for d in filter(str.isdigit, os.listdir("/proc")):
        st = _proc_stat(d)
        if st:
            kids.setdefault(st[1], []).append(int(d))
    out, todo = [], [pid]
    while todo:
        for k in kids.get(todo.pop(), []):
            out.append(k)
            todo.append(k)
    return out


def reap(server):
    """Stop the server and everything it started, and wait until it is
    gone: an orphaned runner keeps the chip and wedges the next boot."""
    family = descendants(server.pid)
    if server.poll() is None:
        server.send_signal(signal.SIGTERM)   # graceful: it stops its backends
        try:
            server.wait(timeout=30)
        except subprocess.TimeoutExpired:
            server.kill()
            server.wait()
    t_kill = time.monotonic() + 5
    while True:
        alive = [p for p in family
                 if (_proc_stat(p) or ("Z",))[0] != "Z"]   # zombies are dead
        if not alive:
            return
        if time.monotonic() > t_kill + 15:
            raise RuntimeError(f"could not stop processes {alive}")
        for p in alive:
            try:
                os.kill(p, signal.SIGKILL if time.monotonic() > t_kill
                        else signal.SIGTERM)
            except ProcessLookupError:
                pass
        time.sleep(0.5)


# --------------------------------------------------------------------- http

class Client:
    def __init__(self, base):
        self.base = base

    def get(self, path, timeout=30):
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return r.read().decode()

    def chat(self, text, max_tokens, stream=False, **params):
        """One /v1/chat/completions exchange -> (text, usage, finish).
        ignore_eos pins the length, so completion_tokens must come back
        equal to max_tokens — asserted here, for every response."""
        body = {"model": NAME, "messages": [{"role": "user",
                                             "content": text}],
                "max_tokens": max_tokens, "ignore_eos": True,
                "stream": stream, **params}
        req = urllib.request.Request(
            self.base + "/v1/chat/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=remaining()) as r:
                if not stream:
                    doc = json.load(r)
                    choice = doc["choices"][0]
                    out = (choice["message"]["content"], doc["usage"],
                           choice["finish_reason"])
                else:
                    parts, usage, finish = [], None, None
                    for raw in r:
                        line = raw.decode().strip()
                        if not line.startswith("data:") \
                                or line == "data: [DONE]":
                            continue
                        ev = json.loads(line[5:])
                        choice = ev["choices"][0]
                        parts.append(choice["delta"].get("content") or "")
                        finish = choice.get("finish_reason") or finish
                        usage = ev.get("usage") or usage
                    assert len(parts) > 2, "SSE stream carried no deltas"
                    out = ("".join(parts), usage, finish)
        except urllib.error.HTTPError as e:
            raise RuntimeError(
                f"chat request failed: HTTP {e.code}: "
                f"{e.read().decode(errors='replace')[:4000]}") from None
        text, usage, finish = out
        assert usage and usage["completion_tokens"] == max_tokens, (
            f"completion_tokens {usage} != max_tokens {max_tokens}")
        assert finish == "length", finish
        return out


def metric(text, name):
    """Sum of a Prometheus series over its label sets."""
    vals = re.findall(rf"^\w*{name}(?:{{[^}}]*}})? ([0-9.eE+-]+)$", text,
                      re.M)
    assert vals, f"/metrics has no {name}"
    return sum(float(v) for v in vals)


# --------------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    rehearsal = args.cpu_rehearsal
    want = "cpu" if rehearsal else "tpu"
    shape = TOY if rehearsal else LLAMA_8B
    env = dict(os.environ, LOCALAI_ALLOW_RANDOM_WEIGHTS="1")
    if rehearsal:
        env.update(JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES=str(args.tp))
    rng = random.Random(SEED)

    # 1. kernels on the chip, in a process of their own
    log("kernel parity: python -m localai_tpu.ops.pallas.parity")
    out = subprocess.run(
        [sys.executable, "-m", "localai_tpu.ops.pallas.parity"]
        + (["--interpret"] if rehearsal else []),
        cwd=HERE, env=env, stdout=subprocess.PIPE, text=True,
        timeout=remaining(), check=True).stdout
    kernels = json.loads(out.strip().splitlines()[-1])
    print(json.dumps({"kernel_parity": kernels}), flush=True)
    assert kernels["device"]["platform"] == want, (
        f"no TPU: the kernel process ran on {kernels['device']}")
    assert kernels["ok"]

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as models:
        write_model(models, shape, args.tp)
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            port = sock.getsockname()[1]
        os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
        server_log = os.path.join(HERE, "chiprun_out", "chip_smoke_server.log")
        log(f"server: python -m localai_tpu run (log: {server_log})")
        with open(server_log, "w") as logf:
            server = subprocess.Popen(
                [sys.executable, "-m", "localai_tpu", "run",
                 "--models-path", models,
                 "--address", f"127.0.0.1:{port}"],
                cwd=HERE, env=env, stdout=logf, stderr=subprocess.STDOUT,
                start_new_session=True)
        try:
            device = serve_and_check(Client(f"http://127.0.0.1:{port}"),
                                     server, rng, shape, args.tp, want)
        except BaseException:
            with open(server_log) as f:
                sys.stderr.write("---- server log (tail) ----\n"
                                 + "".join(f.readlines()[-80:]))
            raise
        finally:
            reap(server)
    log("done")
    print(json.dumps({"ok": True, "device": device,
                      **({"rehearsal": True} if rehearsal else {})}))


def serve_and_check(c, server, rng, shape, tp, want):
    vocab = shape["vocab_size"]
    while True:
        assert server.poll() is None, f"server exited {server.returncode}"
        try:
            c.get("/readyz", timeout=2)
            break
        except OSError:
            remaining()
            time.sleep(0.5)
    log("server is up; first request loads the model "
        "(weights, then the precompile ladder)")

    # 2. traffic. The first request pays the whole cold boot.
    short = prompt(rng, 40, vocab)
    c.chat(short, 16, temperature=0.0)                      # non-streamed
    log("model loaded; first completion served")
    state = json.loads(c.get("/debug/state"))["models"][NAME]
    boot = state["compiles"]
    print(json.dumps({"boot": {
        "programs_built": boot["compiles_total"],
        "of_which_from_compile_cache": boot["compiles_from_cache"],
        "compile_seconds_setup": boot["compile_seconds_total"]}}),
        flush=True)
    c.chat(prompt(rng, 60, vocab), 24, stream=True,          # SSE, sampled
           temperature=0.8, top_k=40, top_p=0.95, seed=7)
    long = prompt(rng, CHUNK + 188, vocab)     # > prefill_chunk: 2 chunks,
    first, _, _ = c.chat(long, 16, temperature=0.0)     # the 2nd continued
    c.chat(long, 16, temperature=0.0)      # reuses the free slot's own rows
    c.chat(prompt(rng, 30, vocab), 24, temperature=0.0,
           frequency_penalty=0.5)          # not spec-eligible: plain bursts
    log("single requests done; concurrent wave")
    wave, errors = [], []

    def one(i, text):
        try:
            c.chat(text, 24, stream=i % 4 == 0, seed=100 + i,
                   temperature=0.0 if i % 2 else 0.7)
        except BaseException as e:      # re-raised below, never swallowed
            errors.append(e)

    for i in range(2 * SLOTS):
        t = threading.Thread(
            target=one, args=(i, prompt(rng, rng.randrange(20, 120), vocab)))
        t.start()
        wave.append(t)
    for t in wave:
        t.join()
    if errors:
        raise errors[0]
    # every slot has been through other requests since: the long prompt's
    # pages now live only in the prefix cache, and this splices them back
    again, _, _ = c.chat(long, 16, temperature=0.0)
    log("wave done; reading the backend's report")

    # 3. the backend's own report
    top = json.loads(c.get("/debug/state"))
    state = top["models"][NAME]
    metrics = c.get("/metrics")
    attention = state["attention"]
    programs = attention["programs"]
    ran = {n: p for n, p in programs.items() if p["dispatches"]}
    report = {
        "device": {k: state[k] for k in ("platform", "device_kind",
                                         "device_count")},
        "device_mem": state["device_mem"],
        "weight_bytes": state["weight_bytes"],
        "compiles": state["compiles"],
        "respawns": top["loader"][NAME]["respawns"],
        "peak_slots_active": state["watermarks"]["peak_slots_active"],
        "packed_prefill": {k: metric(metrics, f"prefill_packed_{k}_total")
                           for k in ("dispatches", "tokens",
                                     "kernel_fallback")},
        "prefix_cache": {k: metric(metrics, f"prefix_cache_{k}_total")
                         for k in ("hits", "hit_rows")},
        "spec": {k: v for k, v in state["spec"].items() if k != "by_mode"},
        "attention": {k: attention[k] for k in
                      ("pallas", "mesh", "kernels_under_shard_map")},
        "programs_ran": {n: f"{p['attention']} x{p['dispatches']}"
                         for n, p in sorted(ran.items())},
        "programs_built_not_run": sorted(set(programs) - set(ran)),
        "greedy_repeat_identical": first == again,
    }
    print(json.dumps({"report": report}, indent=1), flush=True)

    # the device, from the process that holds it
    assert state["platform"] == want, f"runner is on {state['platform']}"
    assert state["device_count"] == tp, state["device_count"]
    if want == "tpu":
        # every device holds its share of the int8 weights (all of them
        # on one chip, a tp-th each on a mesh) — not everything on chip 0
        mem = state["device_mem"]
        assert len(mem) == tp, mem
        share = state["weight_bytes"] / tp
        for d in mem:
            assert d["bytes_in_use"] >= 0.95 * share, (d, share)
        if tp > 1:
            assert max(d["bytes_in_use"] for d in mem) < 2.0 * share + \
                2 ** 30, "weights are not split across the mesh"
    # nothing recompiled, respawned or fell off the kernel path
    assert state["compiles"]["compiles_after_warmup"] == 0, \
        state["last_compiles"]
    assert report["respawns"] == 0
    assert report["packed_prefill"]["kernel_fallback"] == 0
    assert report["peak_slots_active"] == SLOTS, "the wave never filled the slots"
    # every program class of the tick ran, with the attention it should
    classes = {}
    for name, p in ran.items():
        if p["attention"] == "none":    # page clone, offload: no model step
            continue
        kind, _, key = name.partition(":")
        if kind.startswith("prefill_pack"):
            kind = "pack_continued" if key.endswith("True)") else "pack_fresh"
        classes.setdefault(kind, set()).add(p["attention"])
    pallas = want == "tpu"
    decode = "pallas:paged_decode" if pallas else "jnp:paged_gather_append"
    expect = {
        "decode_burst": {decode},
        "spec_tick": {f"{decode} + verify jnp:gather_mixed"},
        "pack_fresh": {"jnp:ragged_fresh"},
        "pack_continued": {"pallas:ragged_prefill" if pallas
                           else "jnp:ragged"},
    }
    assert classes == expect, f"programs that ran: {classes} != {expect}"
    assert any(n.startswith("prefill_pack_head") for n in ran), \
        "no fused (split-head) tick ran"
    assert report["prefix_cache"]["hits"] >= 1, "no prefix-cache splice"
    assert report["prefix_cache"]["hit_rows"] >= CHUNK, report["prefix_cache"]
    assert attention["pallas"] == pallas
    assert attention["kernels_under_shard_map"] == (pallas and tp > 1)
    return {"platform": state["platform"], "kind": state["device_kind"],
            "count": state["device_count"]}


if __name__ == "__main__":
    main()
