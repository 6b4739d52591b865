#!/usr/bin/env python
"""Entry point for ONE cluster host process (ISSUE 20).

Spawned by ``RemoteHostHandle.spawn`` (services/cluster_rpc.py) with a
JSON spec file; builds the model + ClusterHost + control-plane server,
then announces readiness with a single stdout line::

    {"ready": 1, "control": "127.0.0.1:PORT", "kv": "...", "pid": N}

and blocks until the server's drain path signals exit (OP_DRAIN or
SIGTERM). SIGKILL is the crash the control plane exists to survive —
nothing here runs on that path, by design.

Spec format::

    {
      "host_id": 0, "role": "both", "engines": 1, "bind": "127.0.0.1",
      "model": {"kind": "llama-random" | "llama-init",
                "config": {LlamaConfig kwargs}, "dtype": "float32",
                "param_dtype": "bfloat16", "seed": 0},
      "tokenizer": "byte256" | "byte2",
      "engine": {EngineConfig overrides; cache_dtype as a string},
      "precompile": true, "drain_grace_s": 10.0, "drain_linger_s": 2.0
    }

``llama-random`` uses weights.random_params (np seed 0 — bench rigs);
``llama-init`` uses llama.init_params(PRNGKey(seed)) (test rigs). Both
are deterministic, so greedy decode in this process byte-matches the
parent's reference runs — the property every byte gate leans on.

Faults arm from the inherited LOCALAI_FAULTS env at import (same
contract as BackendProcess) or later over OP_FAULT.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import signal
import sys
import threading

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


class _ByteTokenizer256:
    """Raw utf-8 bytes, id 256 = EOS."""
    vocab_size = 257
    eos_token_id = 256

    def encode(self, text):
        return list(text.encode("utf-8", errors="replace"))

    def decode(self, ids, **kw):
        return bytes(i for i in ids if 0 <= i < 256).decode(
            "utf-8", errors="replace")

    def convert_ids_to_tokens(self, ids):
        return [chr(i) if i < 256 else "</s>" for i in ids]

    def get_vocab_size(self):
        return self.vocab_size


class _ByteTokenizer2:
    """tests/conftest.py's tokenizer: ids 2+byte, id 0 = EOS."""
    eos_token_id = 0
    bos_token_id = 1

    def encode(self, text):
        return [2 + b for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        return bytes(i - 2 for i in ids if i >= 2).decode(
            "utf-8", errors="replace")

    def get_vocab_size(self):
        return 258


def _build(spec: dict):
    import jax
    import jax.numpy as jnp

    from localai_tpu.engine import engine as eng
    from localai_tpu.engine.cluster import ClusterHost
    from localai_tpu.models import llama
    from localai_tpu.utils.jaxtools import enable_compilation_cache

    enable_compilation_cache()

    m = spec.get("model") or {}
    dtype = getattr(jnp, m.get("dtype", "float32"))
    # bench rigs build an f32 config but cast the random weights to
    # bf16 (random_params' default) — param_dtype keeps a spawned host
    # bit-identical to such a parent
    pdtype = getattr(jnp, m.get("param_dtype", m.get("dtype", "float32")))
    cfg = llama.LlamaConfig(dtype=dtype, **(m.get("config") or {}))
    kind = m.get("kind", "llama-random")
    if kind == "llama-init":
        params = llama.init_params(
            cfg, jax.random.PRNGKey(int(m.get("seed", 0))), dtype=pdtype)
    elif kind == "llama-random":
        from localai_tpu.engine.weights import random_params
        params = random_params(cfg, dtype=pdtype)
    else:
        raise SystemExit(f"unknown model kind {kind!r}")

    tok = (_ByteTokenizer2() if spec.get("tokenizer") == "byte2"
           else _ByteTokenizer256())

    ek = dict(spec.get("engine") or {})
    if "cache_dtype" in ek:
        ek["cache_dtype"] = getattr(jnp, ek["cache_dtype"])
    if "prefill_buckets" in ek:
        ek["prefill_buckets"] = tuple(ek["prefill_buckets"])
    ecfg = eng.EngineConfig(**ek)

    return ClusterHost.build(
        cfg, params, tok, ecfg,
        host_id=int(spec.get("host_id", 0)),
        engines=int(spec.get("engines", 1)),
        role=spec.get("role", "both"),
        bind=spec.get("bind", "127.0.0.1"),
        eos_token_ids={tok.eos_token_id})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spec", required=True,
                    help="path to the host spec JSON")
    args = ap.parse_args(argv)
    with open(args.spec) as f:
        spec = json.load(f)

    from localai_tpu.services.cluster_rpc import ClusterHostServer

    host = _build(spec)
    host.start(precompile=bool(spec.get("precompile", True)))
    srv = ClusterHostServer(host, bind=spec.get("bind", "127.0.0.1"))
    srv.drain = functools.partial(
        ClusterHostServer.drain, srv,
        grace_s=float(spec.get("drain_grace_s", 10.0)),
        linger_s=float(spec.get("drain_linger_s", 2.0)))
    control = srv.start()

    # SIGTERM = graceful drain (handoff + checkpoint + linger), then exit
    def _term(signum, frame):
        threading.Thread(target=srv.drain, name="sigterm-drain",
                         daemon=True).start()

    signal.signal(signal.SIGTERM, _term)

    print(json.dumps({"ready": 1, "control": control,
                      "kv": host.address, "pid": os.getpid()}), flush=True)

    srv.exit_event.wait()
    srv.stop()
    host.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
