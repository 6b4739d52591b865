"""The comparison that decides ``correct``: the program's model path against
the plain float32 reference, on the run's own checkpoint.

    python -m benchmark.reference.check --config FILE --seed N --traffic FILE
        --scratch DIR [--variants sound[,<control>...]]

The program side is the program's own code, called as the engine calls it:
``engine/weights.py::load_llama_params`` (its cast, its int8 quantization),
``models/llama.py::ragged_prefill`` over packs of up to 1024 tokens in
chunks of 512 (fresh and ``continued``, one and several segments), then
``decode_step`` through the paged KV cache with a shuffled page table — on
the TPU that is the Pallas ragged-prefill and paged-decode kernels. The
sequences have the cell's lengths (``check_lengths`` of the traffic file:
``[prompt tokens, decode steps]``), decode tokens are teacher-forced from
the seed. Compared, against ``llama_f32.forward`` on the same tokens:

  logits_err   ||program - reference|| / ||reference|| over the logits at
               the last prompt position and at every decode step
  kv_err       the same over every K (after RoPE) and V row the program
               left in the cache
  v0_err       the same over the first layer's V rows alone: one matmul
               from the embedding, so what it shows is the precision the
               rows are stored in

The check holds the first ``check.layers`` layers at full width and the
first ``check.vocab_rows`` rows of the embedding and of the head: loading the
whole model a second time would cost more than the window. It makes that part
for itself (the checkpoint is a function of seed and tensor name, so these are
the very tensors the server loads), which lets a run start it beside the
checkpoint maker, before the server takes the chip. A control (``check.controls`` of the configuration file) is the
program with a lower precision switched on; it has to come out as not
correct. Prints one JSON line; exits 0 whatever the verdict (the harness
reads the numbers), non-zero when it could not compare.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

import numpy as np

CHUNK = 512                # the engine's prefill_chunk
PACK_BUCKETS = (512, 1024)  # the engine's pack buckets at that chunk
PAGE = 64                  # the engine's kv_page_size


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64).ravel()
    b = np.asarray(b, np.float64).ravel()
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def sequences(lengths, seed, vocab):
    rng = random.Random(seed ^ 0x5EED)
    return [([rng.randrange(3, vocab) for _ in range(p)],
             [rng.randrange(3, vocab) for _ in range(d)]) for p, d in lengths]


def run_program(ckpt, hf, serving, variant, seqs, context):
    """-> (logits [n_seq][d+1, V], K [n_seq][L, T, KV, hd], V likewise)."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.engine import weights
    from localai_tpu.models import llama
    from localai_tpu.ops import kvcache

    cfg = llama.LlamaConfig.from_hf_config(hf, dtype=jnp.bfloat16)
    quant = variant.get("quantization", serving.get("quantization", ""))
    kv_name = variant.get("kv_cache_dtype", "bfloat16")
    kv_dtype = {"bfloat16": jnp.bfloat16, "int8": jnp.int8}[kv_name]
    params = weights.load_llama_params(ckpt, cfg, dtype=jnp.bfloat16,
                                       quantize=quant)
    S = len(seqs)
    ck, cv = llama.init_cache(cfg, S, context, dtype=kv_dtype, page_size=PAGE)
    mp = context // PAGE
    ptab = np.random.default_rng(1).permutation(S * mp).astype(np.int32)
    ptab = jnp.asarray(ptab.reshape(S, mp))
    ck, cv = (kvcache.with_page_table(c, ptab) for c in (ck, cv))

    prefill = {c: jax.jit(lambda p, *a, c=c: llama.ragged_prefill(
        p, cfg, *a, continued=c)) for c in (False, True)}
    decode = jax.jit(lambda p, t, ln, k, v: llama.decode_step(
        p, cfg, t, ln, k, v))

    done = [0] * S
    logits = [[] for _ in range(S)]
    while any(done[s] < len(seqs[s][0]) for s in range(S)):
        segs, used = [], 0          # one pack: segments up to 1024 tokens
        for s in range(S):
            n = min(CHUNK, len(seqs[s][0]) - done[s])
            if n > 0 and used + n <= PACK_BUCKETS[-1]:
                segs.append((s, done[s], used, n))
                used += n
        N = next(b for b in PACK_BUCKETS if b >= used)
        tok = np.zeros((N,), np.int32)
        pos = np.full((N,), context, np.int32)
        seg_of = np.full((N,), S, np.int32)
        slots = np.full((S,), S, np.int32)
        start, off, ln = (np.zeros((S,), np.int32) for _ in range(3))
        for b, (s, st, o, n) in enumerate(segs):
            tok[o:o + n] = seqs[s][0][st:st + n]
            pos[o:o + n] = np.arange(st, st + n)
            seg_of[o:o + n] = b
            slots[b], start[b], off[b], ln[b] = s, st, o, n
        cont = any(st > 0 for _, st, _, _ in segs)
        lg, ck, cv = prefill[cont](params, *map(jnp.asarray, (
            tok, pos, seg_of, slots, start, off, ln)), ck, cv)
        lg = np.asarray(lg, np.float32)
        for b, (s, st, o, n) in enumerate(segs):
            done[s] = st + n
            if done[s] == len(seqs[s][0]):
                logits[s].append(lg[b])
    steps = max(len(d) for _, d in seqs)
    for j in range(steps):
        live = [j < len(d) for _, d in seqs]
        tok = np.asarray([d[j] if live[s] else 0
                          for s, (_, d) in enumerate(seqs)], np.int32)
        # a slot past its last step writes nothing: position C is dropped
        ln = np.asarray([len(p) + j if live[s] else context
                         for s, (p, _) in enumerate(seqs)], np.int32)
        lg, ck, cv = decode(params, jnp.asarray(tok), jnp.asarray(ln), ck, cv)
        lg = np.asarray(lg, np.float32)
        for s in range(S):
            if live[s]:
                logits[s].append(lg[s])
    rows = []
    for cache in (ck, cv):
        per_layer = [np.asarray(kvcache.rows_to_float(kvcache.gather_all_rows(
            kvcache.layer(cache, li)), jnp.float32))
            for li in range(cfg.num_layers)]
        rows.append(np.stack(per_layer))            # [L, S, C, KV, hd]
    ks = [rows[0][:, s, :len(p) + len(d)] for s, (p, d) in enumerate(seqs)]
    vs = [rows[1][:, s, :len(p) + len(d)] for s, (p, d) in enumerate(seqs)]
    return [np.stack(x) for x in logits], ks, vs


def run_reference(ckpt, hf, layers, weights_precision, seqs):
    from safetensors import safe_open

    from benchmark.reference import llama_f32

    with safe_open(os.path.join(ckpt, "model.safetensors"), "np") as h:
        read = llama_f32.weight_reader(h.get_tensor, weights_precision)
        return llama_f32.forward(read, hf, layers, [
            (p + d, list(range(len(p) - 1, len(p) + len(d))))
            for p, d in seqs])


def compare(prog, ref):
    logits, ks, vs = prog
    return {
        "logits_err": rel_err(np.concatenate(logits),
                              np.concatenate([r[0] for r in ref])),
        "kv_err": rel_err(
            np.concatenate([x.ravel() for x in ks + vs]),
            np.concatenate([r[i].ravel() for i in (1, 2) for r in ref])),
        "v0_err": rel_err(np.concatenate([v[0].ravel() for v in vs]),
                          np.concatenate([r[2][0].ravel() for r in ref])),
    }


def device_info():
    import jax

    d = jax.devices()[0]
    peak = 0
    for dev in jax.local_devices():
        st = dev.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


def check(config, seed, lengths, variants, scratch):
    """The sound program and any controls against the reference, on the
    part of seed ``seed``'s checkpoint that ``config["check"]`` names."""
    from benchmark import make_checkpoint

    chk = config["check"]
    layers = int(chk["layers"])
    vocab = int(chk.get("vocab_rows", config["vocab_size"]))
    hf = {k: config[k] for k in make_checkpoint.HF_KEYS if k in config}
    hf.update(num_hidden_layers=layers, vocab_size=vocab)
    context = int(config["serving"]["context_size"])
    ckpt = os.path.join(scratch, "check_ckpt")
    make_checkpoint.make(config, seed, ckpt, layers=layers, vocab_rows=vocab)
    seqs = sequences(lengths, seed, vocab)
    t0 = time.monotonic()
    ref = run_reference(ckpt, hf, layers, config["precision"]["weights"], seqs)
    t_ref = time.monotonic() - t0
    out = {"layers": layers, "vocab_rows": vocab, "sequences": lengths,
           "reference_s": round(t_ref, 2)}
    for name in variants:
        variant = {} if name == "sound" else chk["controls"][name]
        t0 = time.monotonic()
        try:
            res = compare(run_program(ckpt, hf, config["serving"], variant,
                                      seqs, context), ref)
        except Exception as e:          # a control that crashes has failed
            if name == "sound":
                raise
            res = {"crashed": f"{type(e).__name__}: {e}"[:300]}
        res["seconds"] = round(time.monotonic() - t0, 2)
        out[name] = res
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--variants", default="sound")
    ap.add_argument("--scratch", required=True)
    a = ap.parse_args(argv)
    with open(a.config) as f:
        config = json.load(f)
    with open(a.traffic) as f:
        lengths = json.load(f)["check_lengths"]

    from localai_tpu.backend.runner import require_accelerator
    from localai_tpu.utils.jaxtools import enable_compilation_cache

    enable_compilation_cache()
    require_accelerator()
    out = check(config, a.seed, lengths, a.variants.split(","), a.scratch)
    out["device"] = device_info()
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
