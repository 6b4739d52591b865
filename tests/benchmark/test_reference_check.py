"""The float32 reference against models/llama.py at toy width, and the
controls: the same comparison has to fail when the weights or the KV cache
are rounded to int8. On the chip the same code runs at the cells' widths
(benchmark/reference/check.py); PERF.md has both readings and the limits."""

import json
import os

import numpy as np
import pytest

from benchmark import make_checkpoint, spec
from benchmark.reference import check, llama_f32

TOY = os.path.join(spec.ROOT, "benchmark", "rehearsal", "toy-width.json")
LENGTHS = [[61, 4], [700, 4], [1300, 3]]     # one, two and three 512-chunks


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    with open(TOY) as f:
        config = json.load(f)
    out = check.check(config, 5, LENGTHS,
                      ["sound", "weights_int8", "kv_int8"],
                      str(tmp_path_factory.mktemp("check")))
    return config, out


def _passes(result, limits):
    return all(result[k] <= v for k, v in limits.items())


def test_sound_program_agrees_with_the_reference(toy):
    config, out = toy
    assert _passes(out["sound"], config["check"]["limits"]), out["sound"]


@pytest.mark.parametrize("control", ["weights_int8", "kv_int8"])
def test_lower_precision_comes_out_as_not_correct(toy, control):
    config, out = toy
    assert not _passes(out[control], config["check"]["limits"]), out[control]
    key = "logits_err" if control == "weights_int8" else "v0_err"
    assert out[control][key] > 2 * out["sound"][key]


def test_checkpoint_is_a_function_of_seed_and_name(tmp_path):
    with open(TOY) as f:
        config = json.load(f)
    from safetensors import safe_open

    def tensors(seed, layers, rows, sub):
        d = str(tmp_path / sub)
        make_checkpoint.make(config, seed, d, layers=layers, vocab_rows=rows)
        with safe_open(os.path.join(d, "model.safetensors"), "np") as h:
            return {k: h.get_tensor(k) for k in h.keys()}

    full, part, other = tensors(9, 0, 0, "a"), tensors(9, 1, 100, "b"), \
        tensors(10, 1, 100, "c")
    assert set(part) < set(full)
    assert os.path.exists(tmp_path / "a" / "tokenizer.json")
    for k, v in part.items():                     # a cut of the same model
        assert np.array_equal(v, full[k][:len(v)])
        assert v.dtype == np.float16
    assert len(part["lm_head.weight"]) == 100
    assert not np.array_equal(
        part["model.layers.0.mlp.up_proj.weight"],
        other["model.layers.0.mlp.up_proj.weight"])
    w = full["model.layers.0.mlp.up_proj.weight"].astype(np.float32)
    import ml_dtypes
    assert np.array_equal(w.astype(ml_dtypes.bfloat16).astype(np.float32), w)
    assert w.std() * np.sqrt(w.shape[1]) == pytest.approx(1.0, rel=0.05)


def test_reference_int8_rounding_is_per_output_channel():
    rng = np.random.default_rng(0)
    w = rng.standard_normal((6, 40)).astype(np.float32)     # [out, in]
    r = llama_f32.int8_round(w, 0)
    step = np.abs(w).max(axis=1, keepdims=True) / 127.0
    assert np.all(np.abs(r - w) <= step / 2 + 1e-7)
    assert np.allclose(np.rint(r / step), r / step, atol=1e-4)
    # the embedding is rounded per hidden column
    e = llama_f32.int8_round(w, 1)
    col = np.abs(w).max(axis=0, keepdims=True) / 127.0
    assert np.all(np.abs(e - w) <= col / 2 + 1e-7)
