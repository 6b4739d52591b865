"""Make a configuration's checkpoint from a seed, as a user's would look.

    python -m benchmark.make_checkpoint --config <file> --seed N --out DIR
        [--layers K] [--vocab-rows R]

writes ``DIR/model.safetensors`` (HF names and layout, ``[out, in]``,
float16 holding values already rounded to bfloat16: the loader opens
shards with numpy, which cannot read bfloat16, and casts to bf16 itself,
exactly), ``config.json``, and a word-level tokenizer whose token ``i`` is
the word ``t<i>``. Every tensor is a function of (seed, tensor name, row
block) alone, so ``--layers K --vocab-rows R`` writes the first K layers and
the first R rows of the embedding and the head of the very same model: the
part the correctness check compares, which it makes for itself.

Weights are N(0, 1/fan_in) (the embedding N(0, 1), the norms
1 + N(0, 0.05)), drawn on all host cores with numpy, whose generators
release the GIL. A maker that drew them with ``jax.random`` on the chip was
measured once and was slower (PERF.md, PR 24): it pays a process's way to
the chip and then moves every byte back to the host.
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import time
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ELEMS = 1 << 25          # elements per unit of work (64 MB of float16)
HF_KEYS = ("architectures", "model_type", "hidden_size", "intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "head_dim", "vocab_size", "hidden_act", "max_position_embeddings",
           "rms_norm_eps", "rope_theta", "sliding_window",
           "tie_word_embeddings", "bos_token_id", "eos_token_id")


def tensor_table(cfg: dict, layers: int, vocab_rows: int = 0):
    """[(HF name, shape [out, in] or [n], kind)] in file order."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    V = vocab_rows or cfg["vocab_size"]
    hd = cfg.get("head_dim") or D // cfg["num_attention_heads"]
    H, KV = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    t = [("model.embed_tokens.weight", (V, D), "embed")]
    for i in range(layers):
        p = f"model.layers.{i}."
        t += [(p + "input_layernorm.weight", (D,), "norm"),
              (p + "self_attn.q_proj.weight", (H, D), "linear"),
              (p + "self_attn.k_proj.weight", (KV, D), "linear"),
              (p + "self_attn.v_proj.weight", (KV, D), "linear"),
              (p + "self_attn.o_proj.weight", (D, H), "linear"),
              (p + "post_attention_layernorm.weight", (D,), "norm"),
              (p + "mlp.gate_proj.weight", (F, D), "linear"),
              (p + "mlp.up_proj.weight", (F, D), "linear"),
              (p + "mlp.down_proj.weight", (D, F), "linear")]
    t.append(("model.norm.weight", (D,), "norm"))
    if not cfg.get("tie_word_embeddings", False):
        t.append(("lm_head.weight", (V, D), "linear"))
    return t


def _scale_shift(shape, kind):
    if kind == "norm":
        return 0.05, 1.0
    if kind == "embed":
        return 1.0, 0.0
    return 1.0 / np.sqrt(shape[-1]), 0.0          # [out, in]: fan_in is last


def _to_bf16_in_f16(x: np.ndarray) -> np.ndarray:
    """float32 -> rounded to the nearest bfloat16 (ties to even) -> the
    same value as float16 (exact wherever float16 is normal)."""
    u = x.view(np.uint32)
    u += 0x7FFF + ((u >> 16) & 1)
    u &= 0xFFFF0000
    return x.astype(np.float16)


def block_values(seed: int, name: str, shape, kind, row0: int, rows: int):
    """Rows [row0, row0+rows) of tensor ``name`` as float16 — the one
    definition of the weights."""
    cols = shape[1] if len(shape) == 2 else 1
    rng = np.random.default_rng([seed, zlib.crc32(name.encode()), row0])
    x = rng.standard_normal(rows * cols, dtype=np.float32)
    scale, shift = _scale_shift(shape, kind)
    x *= np.float32(scale)
    if shift:
        x += np.float32(shift)
    return _to_bf16_in_f16(x)


def _work_items(table):
    """(name, shape, kind, row0, rows, byte offset) for every block."""
    items, layout, off = [], {}, 0
    for name, shape, kind in table:
        n_rows = shape[0]
        cols = shape[1] if len(shape) == 2 else 1
        layout[name] = {"dtype": "F16", "shape": list(shape),
                        "data_offsets": [off, off + 2 * n_rows * cols]}
        step = max(1, BLOCK_ELEMS // cols)
        for r0 in range(0, n_rows, step):
            items.append((name, shape, kind, r0, min(step, n_rows - r0),
                          off + 2 * r0 * cols))
        off += 2 * n_rows * cols
    return items, layout, off


def write_weights(path, cfg, layers, seed, vocab_rows=0):
    table = tensor_table(cfg, layers, vocab_rows)
    items, layout, total = _work_items(table)
    header = json.dumps(layout, separators=(",", ":")).encode()
    header += b" " * (-len(header) % 8)
    base = 8 + len(header)
    fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
    try:
        os.write(fd, struct.pack("<Q", len(header)) + header)
        os.ftruncate(fd, base + total)

        def one(item):
            name, shape, kind, r0, rows, off = item
            buf = memoryview(block_values(seed, name, shape, kind, r0,
                                          rows)).cast("B")
            done = 0
            while done < len(buf):
                done += os.pwrite(fd, buf[done:], base + off + done)

        with ThreadPoolExecutor(min(16, os.cpu_count() or 1)) as pool:
            for f in [pool.submit(one, it) for it in items]:
                f.result()
    finally:
        os.close(fd)
    return total


def write_tokenizer(out_dir, vocab_size):
    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2}
    vocab.update({f"t{i}": i for i in range(3, vocab_size)})
    special = [{"id": i, "content": c, "single_word": False, "lstrip": False,
                "rstrip": False, "normalized": False, "special": True}
               for i, c in enumerate(("<unk>", "<s>", "</s>"))]
    with open(os.path.join(out_dir, "tokenizer.json"), "w") as f:
        json.dump({"version": "1.0", "truncation": None, "padding": None,
                   "added_tokens": special, "normalizer": None,
                   "pre_tokenizer": {"type": "WhitespaceSplit"},
                   "post_processor": None, "decoder": None,
                   "model": {"type": "WordLevel", "vocab": vocab,
                             "unk_token": "<unk>"}}, f)
    with open(os.path.join(out_dir, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "bos_token": "<s>", "eos_token": "</s>",
                   "model_max_length": 1 << 20}, f)


def make(config: dict, seed: int, out_dir: str, layers: int = 0,
         vocab_rows: int = 0) -> dict:
    """The whole checkpoint, or (``layers``, ``vocab_rows``) the first
    layers and the first rows of the embedding and the head of the very
    same model: what the correctness check holds. A cut gets no tokenizer."""
    os.makedirs(out_dir, exist_ok=True)
    hf = {k: config[k] for k in HF_KEYS if k in config}
    whole = not layers and not vocab_rows
    layers = layers or hf["num_hidden_layers"]
    t0 = time.monotonic()
    hf_written = {**hf, "num_hidden_layers": layers,
                  "vocab_size": vocab_rows or hf["vocab_size"]}
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump({**hf_written, "torch_dtype": "bfloat16"}, f)
    if whole:
        write_tokenizer(out_dir, hf["vocab_size"])
    nbytes = write_weights(os.path.join(out_dir, "model.safetensors"), hf,
                           layers, seed, vocab_rows)
    return {"bytes": nbytes, "layers": layers,
            "seconds": time.monotonic() - t0}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--vocab-rows", type=int, default=0)
    a = ap.parse_args(argv)
    with open(a.config) as f:
        config = json.load(f)
    print(json.dumps(make(config, a.seed, a.out, a.layers, a.vocab_rows)))


if __name__ == "__main__":
    sys.exit(main())
