"""The granite_hybrid family (models/granite_hybrid.py: Mamba-2 layers beside
no-rope GQA layers) on the served path, at toy width on seeded random
weights: against the plain float32 reference
(benchmark/reference/granite_hybrid_f32.py, which imports nothing of the
program), the chunked state-space dual against the recurrence, the decode
kernel in interpret mode, through the engine (inactive slots, preemption and
resume), and through the runner over HTTP's own path."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import engine as eng
from localai_tpu.engine import sampling
from localai_tpu.models import granite_hybrid as gh
from localai_tpu.ops import kvcache, ssd
from localai_tpu.services.eventlog import EVENTS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_config(dtype="float32", layers=20):
    """Two periods of the published pattern at a narrow width, all four
    multipliers away from 1 (benchmark/rehearsal/granite_hybrid.json)."""
    with open(os.path.join(ROOT, "benchmark", "rehearsal",
                           "granite_hybrid.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        conf["layer_types"] = json.load(f)["layer_types"][:layers]
    conf["num_hidden_layers"] = layers
    conf["check"]["layers"] = layers
    conf["serving"].update(dtype=dtype, context_size=1024)
    return conf


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """The program in float32 (prefill in 2 chunks of a 600-token prompt
    packed beside shorter ones: fresh and ``continued`` segments, chunks of
    256 within them; then up to 16 decode steps through the paged cache and
    the state) against the reference, and the control that holds the
    recurrent state in bfloat16."""
    from benchmark.reference import check

    return check.check(
        _toy_config(), 11, [[70, 4], [130, 6], [5, 3], [600, 16]],
        ["sound", "state_bf16"], str(tmp_path_factory.mktemp("granite")))


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "granite_hybrid_f32.py")) as f:
        assert "localai_tpu" not in f.read()


def test_the_toy_has_the_published_pattern_and_no_multiplier_of_one():
    conf = _toy_config()
    cfg = gh.GraniteHybridConfig.from_hf_config(conf)
    assert cfg.period == ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert cfg.periods == 2 and cfg.ssm_layers == 18 and cfg.attn_layers == 2
    for m in (cfg.embedding_multiplier, cfg.residual_multiplier,
              cfg.logits_scaling,
              cfg.attention_multiplier * cfg.head_dim_ ** 0.5):
        assert abs(m - 1.0) > 0.05


@pytest.mark.parametrize("what", ["logits_err", "kv_err", "state_err",
                                  "conv_err"])
def test_program_agrees_with_the_float32_reference(checked, what):
    assert checked["sound"][what] < 2e-5, checked["sound"]


def test_state_in_bfloat16_does_not(checked):
    low = checked["state_bf16"]
    assert low["state_err"] > 1e-3, low
    assert low["state_err"] > 100 * checked["sound"]["state_err"]


# ---- the recurrence's three forms (ops/ssd.py) ----

def _ssd_inputs(rng, n, H=4, P=8, Ns=16):
    x = rng.standard_normal((n, H, P)).astype(np.float32)
    dt = (0.05 + 0.1 * np.abs(rng.standard_normal((n, H)))).astype(np.float32)
    la = (-dt * np.exp(rng.uniform(0, 2.5, (H,)))).astype(np.float32)
    B = rng.standard_normal((n, Ns)).astype(np.float32)
    C = rng.standard_normal((n, Ns)).astype(np.float32)
    return x, dt, la, B, C


@pytest.mark.parametrize("segs", [
    [(0, 40)],                              # across two chunk boundaries
    [(0, 16), (16, 16)],                    # segments that end on one
    [(0, 37), (37, 0), (37, 9), (46, 33)],  # several, one of them empty
], ids=["one", "aligned", "several"])
def test_chunked_form_is_the_recurrence(segs):
    """Chunks of 16 over a pack of segments, each from a state of its own,
    pad rows NaN: outputs and final states are the token-by-token rule's."""
    rng = np.random.default_rng(len(segs))
    n = segs[-1][0] + segs[-1][1] + 7
    x, dt, la, B, C = _ssd_inputs(rng, n)
    x[-7:] = np.nan
    h0 = rng.standard_normal((len(segs), 4, 8, 16)).astype(np.float32)
    off = jnp.asarray([o for o, _ in segs], jnp.int32)
    ln = jnp.asarray([l for _, l in segs], jnp.int32)
    y, finals = jax.jit(lambda *a: ssd.ssd_chunk(*a, chunk=16))(
        x, dt, la, B, C, h0, off, ln)
    assert np.isfinite(np.asarray(y)).all()
    for b, (o, l) in enumerate(segs):
        yr, hr = ssd.ssd_recurrent(x[o:o + l], dt[o:o + l], la[o:o + l],
                                   B[o:o + l], C[o:o + l], jnp.asarray(h0[b]))
        np.testing.assert_allclose(y[o:o + l], yr, atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(finals[b], hr, atol=2e-5, rtol=1e-4)


def _kernel_inputs(kind, rng, S, H, P, Ns):
    """-> state [2, S, H, P, Ns], (x, dt, la, B, C), three kinds:

    ``random``: normal draws, where every operation rounds.

    ``exact``: small multiples of powers of two and decays of 1, 1/2, 1/4,
    1/8, so that every product, every sum and every partial sum of a row
    of ``y`` is exact in float32, in any order, fused or not. XLA:CPU
    contracts a multiply and an add into one rounding in one program and
    not in another, so on random inputs the interpreted kernel and
    ``ssd_decode`` differ in the last bit here (the compiled kernel on the
    chip does not: ops/pallas/parity.py holds that at 0.0); on these
    inputs "bit-identical" is a statement about the kernel alone.

    ``wide``: a state and a ``C`` whose magnitudes each span 2^-10 .. 2^10
    with full mantissas, so that the products of one row of ``y`` span
    2^-20 .. 2^20, and ``x`` zero, so that the new state is one rounded
    product and bit-identical whatever is fused: a reduction that passes
    through bfloat16 anywhere misses ``y`` by 2^-9 of the largest term."""
    f32 = np.float32
    if kind == "random":
        state = rng.standard_normal((2, S, H, P, Ns)).astype(f32)
        return state, _ssd_inputs(rng, S, H, P, Ns)

    def grid(most, step, shape):
        return (rng.integers(-most, most + 1, shape) * step).astype(f32)

    if kind == "exact":
        state = grid(128, 2.0 ** -4, (2, S, H, P, Ns))
        x = grid(32, 2.0 ** -3, (S, H, P))
        dt = (2.0 ** -rng.integers(3, 7, (S, H))).astype(f32)
        la = (-np.log(2.0) * rng.integers(0, 4, (S, H))).astype(f32)
        assert set(np.asarray(jnp.exp(la)).ravel()) <= {1.0, 0.5, 0.25, 0.125}
        return state, (x, dt, la, grid(32, 2.0 ** -3, (S, Ns)),
                       grid(4, 0.5, (S, Ns)))

    def spread(shape):
        return (rng.uniform(1, 2, shape) * rng.choice([-1, 1], shape)
                * 2.0 ** rng.integers(-10, 11, shape)).astype(f32)

    x, dt, la, B, _ = _ssd_inputs(rng, S, H, P, Ns)
    return spread((2, S, H, P, Ns)), (0 * x, dt, la, B, spread((S, Ns)))


def _bits(a):
    return np.ascontiguousarray(np.asarray(a, np.float32)).view(np.uint32)


@pytest.mark.parametrize("kind", ["random", "exact", "wide"])
@pytest.mark.parametrize("live", [[0] * 6, [1, 0, 1, 1, 0, 0],
                                  [0, 0, 0, 0, 0, 1], [1] * 6],
                         ids=["none", "some", "last", "all"])
def test_decode_kernel_in_interpret_mode_is_the_one_token_form(live, kind):
    """ops/pallas/mamba2_decode.py against ops/ssd.py::ssd_decode on a
    stacked state whose other layer and whose dead slots are NaN: the live
    slots' update; every other block of the state the bits it was; ``y``
    zero for a dead slot and, for a live one, within float32 rounding of
    ``ssd_decode``'s. The tolerance: a row of ``y`` is a sum of Ns
    products; summed in float32 in any order it lies within
    Ns * 2^-24 * sum|products| of the exact sum, so two orders lie within
    twice that of each other, and one more 2^-24 * sum|products| each pays
    for a last bit of the state that differs (``_kernel_inputs``). One
    bfloat16 rounding of a product is 2^-9 of it, 250 times the bound."""
    from localai_tpu.ops.pallas.mamba2_decode import mamba2_decode_pallas

    rng = np.random.default_rng(sum(live))
    S, H, P, Ns = 6, 4, 8, 128
    keep = np.asarray(live, bool)
    state, (x, dt, la, B, C) = _kernel_inputs(kind, rng, S, H, P, Ns)
    state[0] = np.nan
    state[1][~keep] = np.nan
    active = jnp.asarray(keep)
    y0, s0 = ssd.ssd_decode(jnp.asarray(state), 1, x, dt, la, B, C, active)
    y1, s1 = mamba2_decode_pallas(jnp.asarray(state), jnp.int32(1), x, dt,
                                  la, B, C, active, interpret=True)
    y0, s0, y1, s1 = (np.asarray(v) for v in (y0, s0, y1, s1))
    # what no live slot holds: the other layer, the dead slots, NaN and all
    assert (_bits(s1[0]) == _bits(state[0])).all()
    assert (_bits(s1[1][~keep]) == _bits(state[1][~keep])).all()
    assert not y1[~keep].any()
    # the live slots' state
    if kind == "random":
        np.testing.assert_allclose(s1[1][keep], s0[1][keep],
                                   atol=1e-5, rtol=1e-6)
    else:
        assert (_bits(s1[1][keep]) == _bits(s0[1][keep])).all()
    # the live slots' output
    terms = np.abs(s1[1].astype(np.float64) * np.asarray(C)[:, None, None, :])
    bound = 2 * (Ns + 1) * 2.0 ** -24 * terms.sum(-1)
    assert (np.abs(y1 - y0)[keep] <= bound[keep]).all()
    if kind == "exact":
        assert terms[keep].sum(-1).max(initial=0) < 2 ** 11    # 24 bits do
        assert (_bits(y1[keep]) == _bits(y0[keep])).all()
    if kind == "wide" and keep.any():
        lo, hi = terms[keep].min(-1), terms[keep].max(-1)
        assert (lo < 2.0 ** -12).any() and (hi > 2.0 ** 17).any()


# ---- config gates ----

def test_routed_experts_are_refused_with_the_mechanism_named():
    conf = {**_toy_config(), "num_local_experts": 64, "num_experts_per_tok": 6}
    with pytest.raises(ValueError, match="routed expert"):
        gh.GraniteHybridConfig.from_hf_config(conf)


@pytest.mark.parametrize("change, what", [
    ({"layer_types": ["mamba"] * 20}, "both kinds"),
    ({"layer_types": ["mamba", "full_attention"] * 10}, "'mamba' or 'attention'"),
    ({"mamba_n_groups": 8}, "mamba_n_groups"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"mamba_proj_bias": True}, "biases"),
], ids=["one-kind", "unknown-kind", "groups", "rope", "bias"])
def test_what_is_not_built_is_refused_by_name(change, what):
    with pytest.raises(ValueError, match=what):
        gh.GraniteHybridConfig.from_hf_config({**_toy_config(), **change})


def test_a_depth_that_ends_inside_a_period_is_a_period_of_its_own():
    """The check's first six layers (through the first attention layer):
    one period of six."""
    cfg = gh.GraniteHybridConfig.from_hf_config(_toy_config(layers=6))
    assert cfg.period == ("mamba",) * 5 + ("attention",) and cfg.periods == 1


def test_a_contiguous_cache_or_a_mesh_is_refused(byte_tokenizer):
    cfg = gh.GraniteHybridConfig.from_hf_config(_toy_config())
    with pytest.raises(ValueError, match="paged"):
        gh.init_cache(cfg, 2, 64)
    from localai_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(meshlib.MeshPlan(dp=1, tp=2),
                             devices=jax.devices()[:2])
    with pytest.raises(AssertionError, match="mesh is not declared"):
        eng.Engine(CFG, None, byte_tokenizer, eng.EngineConfig(),
                   family=gh, mesh=mesh)


# ---- through the engine ----

CFG = gh.GraniteHybridConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=6,
    period=("mamba", "mamba", "attention"), num_heads=4, num_kv_heads=2,
    ssm_heads=4, ssm_head_dim=32, ssm_state=16, ssm_chunk=16,
    embedding_multiplier=3.0, residual_multiplier=0.5,
    attention_multiplier=0.125, logits_scaling=2.0,
    max_position_embeddings=256, dtype=jnp.float32)


def _engine(tok, **kw):
    params = gh.init_params(CFG, jax.random.PRNGKey(0))
    ecfg = eng.EngineConfig(**{**dict(
        num_slots=1, max_context=128, prefill_buckets=(16, 64),
        decode_burst=4, cache_dtype=jnp.float32), **kw})
    e = eng.Engine(CFG, params, tok, ecfg, family=gh)
    e.start()
    return e


def _greedy(tok, prompt, n, priority=""):
    return eng.GenRequest(
        prompt_ids=tok.encode(prompt),
        params=sampling.SamplingParamsHost(temperature=0.0),
        max_new_tokens=n, ignore_eos=True, priority=priority)


def _collect(out):
    events = []
    while (ev := out.get(timeout=120.0)) is not None:
        events.append(ev)
    return events


def test_engine_gates_and_counters_follow_what_the_family_declares(
        byte_tokenizer):
    assert gh.CAPABILITIES == {"paged", "packed_prefill"}
    e = _engine(byte_tokenizer, num_slots=3)
    try:
        assert e._paged and e._packed and e._pcache is None
        assert e._spec_mode == "off" and not e._per_slot_prefill
        list(e.generate(_greedy(byte_tokenizer, "one live slot of three", 9)))
        st = e.state_snapshot()
        assert st["family"] == "granite_hybrid"
        assert st["capabilities"] == ["packed_prefill", "paged"]
        # 4 mamba layers x 3 slots x (4 x 32 x 16 f32 + 3 x (128 + 32) f32)
        assert st["recurrent_state_bytes"] == 4 * 3 * (2048 + 480) * 4
        walk = st["state_walk"]
        # one slot of three was live in every decode step that ran
        assert walk["slot_steps_live"] > 0
        assert walk["slot_steps_grid"] == 3 * walk["slot_steps_live"]
        assert walk["slot_steps_live"] % 4 == 0        # x 4 mamba layers
    finally:
        e.shutdown()
    with open(eng.__file__) as f:
        src = f.read()
    assert "granite" not in src          # no test of the family's name


def test_an_inactive_slots_state_is_bit_identical_after_a_burst(
        byte_tokenizer):
    """Two slots; the second holds the state of a finished request while
    the first decodes for several bursts."""
    e = _engine(byte_tokenizer, num_slots=2)
    try:
        list(e.generate(_greedy(byte_tokenizer, "fills slot zero", 3)))
        out = e.submit(_greedy(byte_tokenizer, "a longer tenant", 24))
        first = out.get(timeout=120.0)
        assert first is not None and first.error is None
        held = {k: np.asarray(v) for k, v in
                kvcache.state_leaves(e.ck).items()}
        rest = _collect(out)
        assert all(ev.error is None for ev in rest)
        after = {k: np.asarray(v) for k, v in
                 kvcache.state_leaves(e.ck).items()}
    finally:
        e.shutdown()
    # exactly one slot decoded between the two reads: the other's leaves
    # are the very bits they were
    changed = [s for s in range(2)
               if any((held[k][:, s] != after[k][:, s]).any() for k in held)]
    assert len(changed) == 1, changed
    idle = 1 - changed[0]
    for k in held:
        assert (held[k][:, idle] == after[k][:, idle]).all()
        assert held[k][:, idle].any()       # it held a request's state


def test_a_long_prompt_in_chunks_streams_what_one_pack_does(byte_tokenizer):
    """A 100-token prompt through packs of 16 (continued segments, each
    from the slot's state and tail) against one pack of 128."""
    prompt = "state carried from pack to pack " * 3
    outs = []
    for chunk in (16, 128):
        e = _engine(byte_tokenizer, prefill_chunk=chunk,
                    prefill_buckets=(chunk,))
        try:
            outs.append(eng.event_ids(list(e.generate(
                _greedy(byte_tokenizer, prompt, 10)))))
        finally:
            e.shutdown()
    assert len(outs[0]) == 10 and outs[0] == outs[1]


def test_a_preempted_request_resumes_by_reprefill_to_the_same_ids(
        byte_tokenizer):
    e = _engine(byte_tokenizer)
    try:
        base = eng.event_ids(list(e.generate(
            _greedy(byte_tokenizer, "background work", 40, "low"))))
        EVENTS.clear()
        low = _greedy(byte_tokenizer, "background work", 40, "low")
        out_low = e.submit(low)
        first = out_low.get(timeout=120.0)
        out_high = e.submit(_greedy(byte_tokenizer, "urgent", 6, "high"))
        high = _collect(out_high)
        events = [first] + _collect(out_low)
        assert all(ev.error is None for ev in high + events)
        assert [ev for ev in EVENTS.events() if ev["event"] == "preempt"
                and ev["rid"] == low.request_id]
        stats = e.metrics()["scheduler"]
        assert stats["resumes"] >= 1 and stats["resume_reprefills"] >= 1
        assert stats["resume_restore_rows"] == 0      # nothing to resume from
        spans = [s for s in e.tracer.spans() if s["name"] == "resume"]
        assert spans and spans[-1]["args"]["reprefill_rows"] > \
            len(low.prompt_ids) - 40
        adm = [s for s in e.tracer.spans() if s["name"] == "admission"]
        assert adm and all(s["args"]["state_reset"] for s in adm)
    finally:
        e.shutdown()
    assert eng.event_ids(events) == base and len(base) == 40


# ---- through the runner ----

def _write_checkpoint(tmp_path, **change):
    from benchmark import make_checkpoint

    d = str(tmp_path / "ckpt")
    make_checkpoint.make(_toy_config(layers=10), 3, d)
    if change:
        with open(os.path.join(d, "config.json")) as f:
            c = json.load(f)
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump({**c, **change}, f)
    return d


def _load(d, num_slots=6, rpc_pool=None, **kw):
    from localai_tpu.backend import contract_pb2 as pb
    from localai_tpu.backend.runner import EngineServicer

    sv = EngineServicer(rpc_pool=rpc_pool)
    res = sv.LoadModel(pb.ModelOptions(
        model=d, context_size=128, num_slots=num_slots, dtype="float32",
        prefill_buckets=[32], **kw), None)
    return sv, res


def test_checkpoint_has_hf_names_split_at_load_and_a_tied_head(tmp_path):
    from safetensors import safe_open

    d = _write_checkpoint(tmp_path)
    with open(os.path.join(d, "config.json")) as f:
        hf = json.load(f)
    assert hf["model_type"] == "granitemoehybrid"
    with safe_open(os.path.join(d, "model.safetensors"), "np") as h:
        names = set(h.keys())
        in_proj = h.get_tensor("model.layers.0.mamba.in_proj.weight")
        mlp_in = h.get_tensor("model.layers.0.shared_mlp.input_linear.weight")
    assert "lm_head.weight" not in names
    assert "model.layers.0.mamba.conv1d.bias" in names
    assert "model.layers.5.self_attn.q_proj.weight" in names
    cfg = gh.GraniteHybridConfig.from_hf_config(hf, dtype=jnp.float32)
    params = gh.load_hf_params(d, cfg, dtype=jnp.float32)
    lay = params["layers"]
    Di, Ch = cfg.d_inner, cfg.conv_channels
    assert lay["ssm_in_z"].shape == (1, 9, cfg.hidden_size, Di)
    np.testing.assert_array_equal(lay["ssm_in_z"][0, 0], in_proj[:Di].T)
    np.testing.assert_array_equal(lay["ssm_in_xbc"][0, 0],
                                  in_proj[Di:Di + Ch].T)
    np.testing.assert_array_equal(lay["ssm_in_dt"][0, 0], in_proj[Di + Ch:].T)
    F = cfg.intermediate_size
    np.testing.assert_array_equal(lay["w_gate"][0, 0], mlp_in[:F].T)
    np.testing.assert_array_equal(lay["w_up"][0, 0], mlp_in[F:].T)
    assert lay["wq"].shape[:2] == (1, 1) and "lm_head" not in params
    assert lay["ssm_A_log"].shape == (1, 9, cfg.ssm_heads)


def test_runner_serves_a_granitemoehybrid_checkpoint(tmp_path, monkeypatch):
    from localai_tpu.backend import contract_pb2 as pb

    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    sv, res = _load(_write_checkpoint(tmp_path), mesh_tp=1)
    try:
        assert res.success, res.message
        assert sv.engine.family is gh and sv.engine._paged

        class _Ctx:
            def is_active(self):
                return True

            def abort(self, code, msg):
                raise AssertionError(f"abort: {code} {msg}")

        chunks = list(sv.PredictStream(pb.PredictOptions(
            prompt="t5 t9 t40 t7", max_tokens=6, temperature=0.0,
            ignore_eos=True), _Ctx()))
        assert "".join(c.message.decode("utf-8", "replace") for c in chunks)
    finally:
        if getattr(sv, "engine", None) is not None:
            sv.engine.shutdown()


@pytest.mark.parametrize("num_slots, threads", [(6, 16), (18, 256)])
def test_more_slots_than_rpc_threads_raise_the_servers_pool(
        tmp_path, monkeypatch, num_slots, threads):
    """A streamed request holds a gRPC thread for its life: a model with
    more slots than the server's 16 threads raises them at LoadModel, and
    one with fewer keeps the pool it was measured with."""
    from localai_tpu.backend.service import RpcPool

    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    pool = RpcPool(max_workers=16)
    inside, gate = threading.Semaphore(0), threading.Event()
    sv, res = _load(_write_checkpoint(tmp_path), num_slots=num_slots,
                    rpc_pool=pool, mesh_tp=1)
    try:
        assert res.success, res.message
        # as many handlers as the pool allows run at once, and no more
        futs = [pool.submit(lambda: (inside.release(), gate.wait(30)))
                for _ in range(threads + 4)]
        for _ in range(threads):
            assert inside.acquire(timeout=30)
        assert not inside.acquire(timeout=0.3)
        gate.set()
        for f in futs:
            f.result(timeout=30)
    finally:
        gate.set()
        pool.shutdown(wait=True)
        if getattr(sv, "engine", None) is not None:
            sv.engine.shutdown()


def test_runner_refuses_routed_experts_and_a_mesh(tmp_path, monkeypatch):
    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")
    d = _write_checkpoint(tmp_path, num_local_experts=64)
    _, res = _load(d, mesh_tp=1)
    assert not res.success and "routed expert" in res.message
    d2 = _write_checkpoint(tmp_path / "x")
    _, res = _load(d2, mesh_tp=4)
    assert not res.success and "one device" in res.message


# ---- over HTTP: model manager -> spawned runner -> the same Engine ----

GRANITE_YAML = """\
name: granite
backend: tpu-llm
parameters:
  model: granite-ckpt
  max_tokens: 8
context_size: 128
num_slots: 6
dtype: float32
prefill_buckets: [32]
mesh:
  tp: 1
  dp: 1
template:
  completion: "{{ Input }}"
  chat_message: "{{ Content }}"
  chat: "{{ Input }}"
"""


@pytest.mark.e2e
def test_six_slots_serve_the_family_over_http(tmp_path):
    """An HF-named safetensors checkpoint with ``model_type:
    granitemoehybrid`` in a models directory: the model manager spawns the
    runner, which loads it through the family and serves
    /v1/chat/completions; /debug/state names the family and its state."""
    import asyncio
    import threading

    import httpx

    from benchmark import make_checkpoint
    from localai_tpu.api.app import build_app, run_app
    from localai_tpu.capabilities import Capabilities
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import scan_models_dir
    from localai_tpu.modelmgr.loader import ModelLoader
    from localai_tpu.modelmgr.process import free_port

    make_checkpoint.make(_toy_config(layers=10), 3,
                         str(tmp_path / "granite-ckpt"))
    (tmp_path / "granite.yaml").write_text(GRANITE_YAML)
    port = free_port()
    app_config = AppConfig(models_path=str(tmp_path),
                           address=f"127.0.0.1:{port}")
    loader = ModelLoader(health_attempts=600, health_interval_s=0.2)
    caps = Capabilities(app_config, loader, scan_models_dir(str(tmp_path)))
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

    threading.Thread(target=run, daemon=True).start()
    assert started.wait(10)
    base = f"http://127.0.0.1:{port}"
    try:
        r = httpx.post(f"{base}/v1/chat/completions", json={
            "model": "granite", "max_tokens": 8, "ignore_eos": True,
            "temperature": 0.0,
            "messages": [{"role": "user", "content": "t5 t9 t40 t7"}],
        }, timeout=600.0)
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["usage"]["completion_tokens"] == 8
        assert body["choices"][0]["finish_reason"] == "length"
        st = httpx.get(f"{base}/debug/state", timeout=60.0).json()
        model = st["models"]["granite"]
        assert model["family"] == "granite_hybrid"
        assert model["recurrent_state_bytes"] > 0
        assert model["state_walk"]["slot_steps_live"] > 0
        assert 6 * model["state_walk"]["slot_steps_live"] == \
            model["state_walk"]["slot_steps_grid"]
    finally:
        loop.call_soon_threadsafe(loop.stop)
        loader.stop_all()
