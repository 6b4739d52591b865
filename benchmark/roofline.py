"""The least work a kernel or a step must do, computed from shapes, and the
peaks it is held against. Kept with the benchmark so that no PR that claims
a gain can change the yardstick.

A roofline share is (least time the chip could take) / (time it took). The
functions here count the *least* bytes and operations, so a share above
100% is a fault in the count or in the timing, never a fast kernel.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(table)})")
    return table[device_kind]


def weight_param_counts(hf: dict) -> dict:
    """Parameters by group for a Llama/Mistral decoder config."""
    D, F, V = hf["hidden_size"], hf["intermediate_size"], hf["vocab_size"]
    L = hf["num_hidden_layers"]
    hd = hf.get("head_dim") or D // hf["num_attention_heads"]
    H, KV = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
    per_layer = D * H + 2 * D * KV + H * D + 3 * D * F
    return {"layers": L * per_layer, "norms": (2 * L + 1) * D,
            "embed": V * D,
            "head": 0 if hf.get("tie_word_embeddings") else V * D}


def kv_bytes_per_token(hf: dict, kv_itemsize: int = 2) -> int:
    hd = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return 2 * hf["num_hidden_layers"] * hf["num_key_value_heads"] * hd \
        * kv_itemsize


def decode_step_least_bytes(hf: dict, weight_itemsize: int,
                            live_kv_tokens: float, batch: float,
                            kv_itemsize: int = 2) -> float:
    """Least HBM bytes one decode step of ``batch`` sequences must read:
    every layer matmul weight and the head once (at the width they are
    stored in), the norms, one embedding row a sequence, and every live KV
    row once. Writes, activations and scales are left out: the least."""
    p = weight_param_counts(hf)
    weights = (p["layers"] + p["head"]) * weight_itemsize + p["norms"] * 2
    embed_rows = batch * hf["hidden_size"] * weight_itemsize
    return weights + embed_rows + live_kv_tokens * kv_bytes_per_token(
        hf, kv_itemsize)


def decode_step_least_flops(hf: dict, live_kv_tokens: float,
                            batch: float) -> float:
    """Least floating-point operations of one decode step: 2 per weight
    per sequence in the layer matmuls and the head, 4 * hd per head per
    live KV row in attention."""
    p = weight_param_counts(hf)
    hd = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    attn = 4 * hf["num_hidden_layers"] * hf["num_attention_heads"] * hd \
        * live_kv_tokens
    return 2 * batch * (p["layers"] + p["head"]) + attn


def least_seconds(bytes_: float, flops: float, peak: dict) -> float:
    """The roofline: the larger of bytes over bandwidth and operations
    over the bf16 peak."""
    return max(bytes_ / peak["hbm_bytes_per_s"],
               flops / peak["bf16_flops_per_s"])
