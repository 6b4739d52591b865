"""Model correctness: prefill+decode must agree with a naive full forward."""

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.models import llama


def test_prefill_decode_consistency(tiny_llama):
    """Decoding token-by-token must match prefilling the whole prompt."""
    cfg, params = tiny_llama
    key = jax.random.PRNGKey(1)
    T = 12
    tokens = jax.random.randint(key, (1, T), 0, cfg.vocab_size, jnp.int32)

    # path A: prefill all T tokens
    ck, cv = llama.init_cache(cfg, 2, 32)
    logits_full, _, _ = llama.prefill(
        params, cfg, tokens, jnp.array([T], jnp.int32), ck, cv,
        jnp.array([0], jnp.int32), jnp.array([0], jnp.int32),
    )

    # path B: prefill T-1 then decode the last token
    ck, cv = llama.init_cache(cfg, 2, 32)
    _, ck, cv = llama.prefill(
        params, cfg, tokens[:, : T - 1], jnp.array([T - 1], jnp.int32), ck, cv,
        jnp.array([0], jnp.int32), jnp.array([0], jnp.int32),
    )
    # decode runs over ALL slots; slot 1 is inactive padding
    step_tokens = jnp.array([tokens[0, T - 1], 0], jnp.int32)
    lengths = jnp.array([T - 1, 0], jnp.int32)
    logits_step, _, _ = llama.decode_step(params, cfg, step_tokens, lengths, ck, cv)

    # bf16 tolerance: decode's append-attention (self-score held in
    # registers) reduces in a different order than prefill; verified exact
    # (1e-6) in float32
    np.testing.assert_allclose(
        np.asarray(logits_full[0]), np.asarray(logits_step[0]), rtol=4e-2, atol=4e-2
    )


def test_prefill_padding_invariance(tiny_llama):
    """Right-padding must not change the last-token logits."""
    cfg, params = tiny_llama
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, cfg.vocab_size, jnp.int32)
    padded = jnp.pad(tokens, ((0, 0), (0, 8)))

    ck, cv = llama.init_cache(cfg, 1, 32)
    a, _, _ = llama.prefill(params, cfg, tokens, jnp.array([8], jnp.int32), ck, cv,
                            jnp.array([0], jnp.int32), jnp.array([0], jnp.int32))
    ck, cv = llama.init_cache(cfg, 1, 32)
    b, _, _ = llama.prefill(params, cfg, padded, jnp.array([8], jnp.int32), ck, cv,
                            jnp.array([0], jnp.int32), jnp.array([0], jnp.int32))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-2)


def test_chunked_prefill_matches(tiny_llama):
    """Prefilling in two chunks (prefix continuation) must match one shot."""
    cfg, params = tiny_llama
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 16), 0, cfg.vocab_size, jnp.int32)

    ck, cv = llama.init_cache(cfg, 1, 32)
    one, _, _ = llama.prefill(params, cfg, tokens, jnp.array([16], jnp.int32), ck, cv,
                              jnp.array([0], jnp.int32), jnp.array([0], jnp.int32))

    ck, cv = llama.init_cache(cfg, 1, 32)
    _, ck, cv = llama.prefill(params, cfg, tokens[:, :8], jnp.array([8], jnp.int32), ck, cv,
                              jnp.array([0], jnp.int32), jnp.array([0], jnp.int32))
    two, _, _ = llama.prefill(params, cfg, tokens[:, 8:], jnp.array([8], jnp.int32), ck, cv,
                              jnp.array([0], jnp.int32), jnp.array([8], jnp.int32),
                              continued=True)
    # bf16 tolerance (verified exact in float32): continued-prefill attention
    # splits cache-prefix and chunk-local scores, changing reduction order
    np.testing.assert_allclose(np.asarray(one), np.asarray(two), rtol=4e-2, atol=4e-2)


def test_gqa_heads_shapes(tiny_llama):
    cfg, params = tiny_llama
    assert cfg.q_per_kv == 2
    ck, cv = llama.init_cache(cfg, 4, 16)
    assert ck.shape == (cfg.num_layers, 4, 16, cfg.num_kv_heads, cfg.head_dim_)


def test_hf_config_parsing():
    hf = {
        "vocab_size": 128256, "hidden_size": 4096, "intermediate_size": 14336,
        "num_hidden_layers": 32, "num_attention_heads": 32, "num_key_value_heads": 8,
        "rope_theta": 500000.0, "rms_norm_eps": 1e-5, "max_position_embeddings": 131072,
        "rope_scaling": {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
                          "high_freq_factor": 4.0, "original_max_position_embeddings": 8192},
    }
    cfg = llama.LlamaConfig.from_hf_config(hf)
    assert cfg.num_kv_heads == 8
    assert cfg.rope_scaling_type == "llama3"
    assert cfg.rope_scaling_factor == 8.0


def test_int8_quantized_model_close_to_fp():
    """Weight-only int8: logits stay close, greedy path runs end-to-end."""
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_position_embeddings=128,
        dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = llama.quantize_params(params)
    # dequantized weights reconstruct the originals within half a step
    w = np.asarray(params["layers"]["w_gate"], np.float32)
    wq = qparams["layers"]["w_gate"]
    deq = np.asarray(wq["q"], np.float32) * np.asarray(wq["s"])
    step = np.asarray(wq["s"])
    assert np.all(np.abs(deq - w) <= step * 0.51 + 1e-7)

    S, C, T = 2, 32, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (S, T), 0,
                                cfg.vocab_size, jnp.int32)
    seq = jnp.full((S,), T, jnp.int32)
    slots = jnp.arange(S, dtype=jnp.int32)
    start = jnp.zeros((S,), jnp.int32)

    def run(p):
        ck, cv = llama.init_cache(cfg, S, C, jnp.float32)
        logits, ck, cv = llama.prefill(p, cfg, tokens, seq, ck, cv, slots, start)
        d, ck, cv = llama.decode_step(p, cfg,
                                      jnp.argmax(logits, -1).astype(jnp.int32),
                                      seq, ck, cv)
        return logits, d

    ref_l, ref_d = jax.jit(run)(params)
    q_l, q_d = jax.jit(run)(qparams)
    assert np.all(np.isfinite(np.asarray(q_l)))
    # int8 weight-only is near-lossless: logits track the fp model
    np.testing.assert_allclose(np.asarray(q_l), np.asarray(ref_l),
                               atol=0.12, rtol=0.1)
    np.testing.assert_allclose(np.asarray(q_d), np.asarray(ref_d),
                               atol=0.12, rtol=0.1)


def test_int4_grouped_quantization_layout_and_roundtrip():
    """int4 {q, s} leaves: jnp.int4 storage, group scales on the
    contraction axis, reconstruction within half a quantization step."""
    from localai_tpu.ops import quant

    rng = np.random.default_rng(3)
    w = rng.standard_normal((2, 256, 96)).astype(np.float32)
    leaf = quant.quantize_weight_int4(w, group=128)
    assert leaf["q"].dtype == jnp.int4
    assert leaf["q"].shape == (2, 256, 96)
    assert leaf["s"].shape == (2, 2, 1, 96)       # [L, in/g, 1, out]
    assert quant.is_grouped(leaf)
    deq = np.asarray(quant.mat(leaf, jnp.float32))
    step = np.asarray(leaf["s"]).repeat(128, axis=1).reshape(2, 256, 96)
    assert np.all(np.abs(deq - w) <= step * 0.51 + 1e-7)

    # a non-divisible contraction axis picks the largest viable group
    # instead (96 -> one group of 96); truly tiny axes fall back to int8
    near = quant.quantize_weight_int4(w[:, :96], group=128)
    assert near["q"].dtype == jnp.int4
    assert near["s"].shape == (2, 1, 1, 96)
    small = quant.quantize_weight_int4(w[:, :12], group=128)
    assert small["q"].dtype == jnp.int8
    assert not quant.is_grouped(small)

    # the shard_divisor constraint: llama-2's 11008 FFN with tp=8 can't
    # use 128 (86 groups) — picks 86 (128 groups, divisible by 8)
    assert quant.pick_int4_group(11008, 128, 1) == 128
    assert quant.pick_int4_group(11008, 128, 8) == 86


def test_int4_quantized_model_close_to_fp():
    """Weight-only int4 (group scales, embed/lm_head int8): logits track
    the fp model within 4-bit rounding, greedy path runs end-to-end."""
    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=32,
        max_position_embeddings=128, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = llama.quantize_params(params, bits=4)
    assert qparams["layers"]["w_gate"]["q"].dtype == jnp.int4
    assert qparams["embed"]["q"].dtype == jnp.int8   # embeds stay int8

    S, C, T = 2, 32, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (S, T), 0,
                                cfg.vocab_size, jnp.int32)
    seq = jnp.full((S,), T, jnp.int32)
    slots = jnp.arange(S, dtype=jnp.int32)
    start = jnp.zeros((S,), jnp.int32)
    # decode a FIXED token (not each model's own argmax) so the fp-vs-int4
    # comparison measures rounding noise, not token divergence
    next_tok = jax.random.randint(jax.random.PRNGKey(2), (S,), 0,
                                  cfg.vocab_size, jnp.int32)

    def run(p):
        ck, cv = llama.init_cache(cfg, S, C, jnp.float32)
        logits, ck, cv = llama.prefill(p, cfg, tokens, seq, ck, cv, slots,
                                       start)
        d, ck, cv = llama.decode_step(p, cfg, next_tok, seq, ck, cv)
        return logits, d

    ref_l, ref_d = jax.jit(run)(params)
    q_l, q_d = jax.jit(run)(qparams)
    assert np.all(np.isfinite(np.asarray(q_l)))

    # the exactness contract: the device-side grouped dequant (mat()'s
    # reshape * scale inside the jitted forward) must equal running the
    # HOST-dequantized dense weights through the same model
    dq_l, dq_d = jax.jit(run)(llama.dequantize_params(qparams, jnp.float32))
    np.testing.assert_allclose(np.asarray(q_l), np.asarray(dq_l),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(q_d), np.asarray(dq_d),
                               rtol=2e-4, atol=2e-4)

    # quality sanity: 4-bit rounding on a RANDOM-init model is the worst
    # case (no structure for RTN to preserve), so the gate is loose —
    # logit direction broadly survives
    def cos_rows(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        num = (a * b).sum(-1)
        den = np.linalg.norm(a, axis=-1) * np.linalg.norm(b, axis=-1)
        return num / np.maximum(den, 1e-12)

    assert np.all(cos_rows(q_l, ref_l) > 0.85), cos_rows(q_l, ref_l)
    assert np.all(cos_rows(q_d, ref_d) > 0.85), cos_rows(q_d, ref_d)


def test_int4_quantization_wired_through_loadmodel(tmp_path):
    """YAML/proto quantization="int4" -> the DEVICE weights are actually
    jnp.int4 with grouped scales (w_down gets group 128; wq's in-axis 64
    gets the largest viable group, 64), embed stays int8, and generation
    still streams."""
    import os

    from localai_tpu.backend import contract_pb2 as pb
    from localai_tpu.backend.runner import EngineServicer
    from tests.tinymodel import write_tiny_checkpoint

    d = str(tmp_path / "m")
    write_tiny_checkpoint(d)
    os.environ["LOCALAI_PRECOMPILE"] = "0"

    class _Ctx:
        def is_active(self):
            return True

    svc = EngineServicer()
    res = svc.LoadModel(pb.ModelOptions(
        model=d, dtype="float32", quantization="int4", num_slots=2,
        context_size=64, prefill_buckets=[16], mesh_tp=1, mesh_dp=1), None)
    assert res.success, res.message
    try:
        ly = svc.engine.params["layers"]
        assert ly["w_down"]["q"].dtype == jnp.int4     # in-axis 128: grouped
        assert ly["w_down"]["s"].ndim == ly["w_down"]["q"].ndim + 1
        assert ly["wq"]["q"].dtype == jnp.int4         # in-axis 64: group 64
        assert svc.engine.params["embed"]["q"].dtype == jnp.int8
        chunks = list(svc.PredictStream(pb.PredictOptions(
            prompt="hello world", max_tokens=5, temperature=0.0,
            ignore_eos=True), _Ctx()))
        assert sum(c.tokens for c in chunks if c.tokens) >= 1
    finally:
        svc.engine.shutdown()
