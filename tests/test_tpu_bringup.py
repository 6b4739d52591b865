"""Device bring-up rules (ISSUE 21): where the compile cache lives, which
process may touch jax, and that nothing quietly serves from the CPU."""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(code: str, cwd: str, **env) -> str:
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_COMPILATION_CACHE_DIR", "PYTHONPATH")}
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, text=True, check=True,
        env={**base, "PYTHONPATH": REPO, **env}, stdout=subprocess.PIPE)
    return out.stdout.strip().splitlines()[-1]


_CACHE_PROBE = """
import jax
from localai_tpu.utils.jaxtools import enable_compilation_cache
before = jax.config.jax_compilation_cache_dir
print(repr((before, enable_compilation_cache(),
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)))
"""


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    """Unset: <checkout>/.jax_cache, whatever the cwd — never ~, never a
    temp directory, nothing that varies by pid or time."""
    want = os.path.join(REPO, ".jax_cache")
    seen = {_child(_CACHE_PROBE, cwd, HOME=str(tmp_path / "home"))
            for cwd in (REPO, str(tmp_path), "/")}
    assert seen == {repr((None, want, want, 0.0))}
    assert not (tmp_path / "home").exists()


def test_compile_cache_dir_from_the_environment_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself; the helper
    sets no directory in code (only the thresholds)."""
    where = str(tmp_path / "elsewhere")
    got = _child(_CACHE_PROBE, str(tmp_path), JAX_COMPILATION_CACHE_DIR=where)
    assert got == repr((where, where, where, 0.0))
    assert not os.path.exists(where)    # jax creates it on first write


def test_runner_refuses_a_silent_cpu_fallback(monkeypatch):
    """jax falls back to the CPU with a warning when it cannot open the
    chip; the runner must not then serve from it — unless the
    environment names cpu, as the tests' own children do."""
    from localai_tpu.backend.runner import require_accelerator

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert require_accelerator() == "cpu"
    for env in ("tpu,cpu", None):    # what TPU hosts export; nothing
        monkeypatch.delenv("JAX_PLATFORMS")
        if env:
            monkeypatch.setenv("JAX_PLATFORMS", env)
        with pytest.raises(RuntimeError, match="no TPU"):
            require_accelerator()


def test_http_process_never_imports_jax(tmp_path):
    """One process owns a chip, and it is the runner: nothing the server
    process imports may pull jax in (cli `worker` aside)."""
    got = _child(
        "import sys\n"
        "import localai_tpu.cli, localai_tpu.startup, localai_tpu.api.app\n"
        "import localai_tpu.api.localai_routes, localai_tpu.api.openai_routes\n"
        "import localai_tpu.capabilities, localai_tpu.modelmgr.loader\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'jax'))",
        str(tmp_path))
    assert got == "[]"


def test_system_devices_come_from_the_backend_report():
    """/system and /api/p2p answer from GetState, per device, and know
    nothing before a model is loaded."""
    from localai_tpu.api import localai_routes as routes

    def state_with(models: dict):
        def get(name):
            payload = json.dumps({"state": models[name]}).encode()
            reply = types.SimpleNamespace(message=payload)
            return types.SimpleNamespace(client=types.SimpleNamespace(
                get_state=lambda timeout: reply))

        loader = types.SimpleNamespace(list_loaded=lambda: list(models),
                                       get=get)
        return types.SimpleNamespace(caps=types.SimpleNamespace(
            loader=loader))

    assert routes._backend_devices(state_with({})) == []
    mem = [{"id": i, "device_kind": "TPU v5 lite", "bytes_in_use": 1}
           for i in range(4)]
    got = routes._backend_devices(state_with({
        "a": {"platform": "tpu", "device_mem": mem},
        "b": {"platform": "tpu", "device_mem": mem[:1]}}))
    assert [d["id"] for d in got] == [0, 1, 2, 3]
    assert got[0] == {"id": 0, "platform": "tpu", "kind": "TPU v5 lite",
                      "models": ["a", "b"]}
    assert got[3]["models"] == ["a"]


def test_parity_checks_hold_in_interpret_mode():
    """chip_smoke.py's first phase (ops/pallas/parity.py), at toy head
    shapes through the Pallas interpreter: keeps the script itself from
    rotting between chip runs."""
    from localai_tpu.ops.pallas import parity

    small = dict(interpret=True, heads=(2, 2, 16), page=8)
    for err in (parity.check_paged_decode(False, **small),
                parity.check_paged_decode(True, **small),
                parity.check_contiguous_decode(**small),
                parity.check_ragged_prefill(64, **small)):
        assert err <= 5e-3    # interpreter: bf16 output rounding only


def test_meshed_attention_kernels_match_jnp():
    """On a mesh the Pallas kernels run under shard_map over tp
    (Mosaic kernels cannot be GSPMD-partitioned). Whole-model decode and
    continued packed prefill on a tp=2 CPU mesh, kernels in the
    strict TPU interpreter (out-of-bounds reads raise), against the jnp
    path on one device: the head split must line up with the weights'."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas import tpu as pltpu

    from localai_tpu.models import llama
    from localai_tpu.ops import kvcache
    from localai_tpu.parallel import mesh as meshlib, sharding as shardlib

    cfg = llama.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=8, num_kv_heads=4, max_position_embeddings=128,
        dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    S, C, pg, N = 4, 64, 8, 16
    rng = np.random.default_rng(0)
    ptab = jnp.asarray(rng.permutation(S * (C // pg)).astype(np.int32)
                       .reshape(S, C // pg))
    mesh = meshlib.make_mesh(meshlib.MeshPlan(tp=2), jax.devices()[:2])
    jnp_cfg = dataclasses.replace(cfg, attn=llama.AttnTarget(pallas=False))
    mesh_cfg = dataclasses.replace(
        cfg, attn=llama.AttnTarget(pallas=True, mesh=mesh))
    sharded = shardlib.shard_params(mesh, params)
    tokens = jnp.asarray([3, 5, 7, 9], jnp.int32)
    lengths = jnp.asarray([0, 8, 13, 40], jnp.int32)
    # a 16-token pack: slot 1 continues from row 8, slot 3 from row 40
    seg = dict(slots=[1, 3, S, S], start=[8, 40, 0, 0], off=[0, 6, 0, 0],
               len=[6, 9, 0, 0])
    seg = {k: jnp.asarray(v, jnp.int32) for k, v in seg.items()}
    seg_of = jnp.asarray([0] * 6 + [1] * 9 + [S], jnp.int32)
    positions = jnp.asarray([*range(8, 14), *range(40, 49), C], jnp.int32)
    pack = jnp.asarray(rng.integers(3, 250, size=N).astype(np.int32))

    def decode(c):
        return jax.jit(lambda p, a, b: llama.decode_step(
            p, c, tokens, lengths, a, b)[0])

    def prefill(c):
        return jax.jit(lambda p, a, b: llama.ragged_prefill(
            p, c, pack, positions, seg_of, seg["slots"], seg["start"],
            seg["off"], seg["len"], a, b, continued=True)[0][:2])

    for kv_dtype in (jnp.float32, jnp.int8):
        def filled(c):
            rows = jnp.asarray(rng.standard_normal(c["pages"].shape),
                               jnp.float32)
            if "scales" not in c:
                return {"pages": rows, "ptab": ptab}
            q, s = kvcache.quantize(rows)
            return {"pages": q, "scales": s, "ptab": ptab}

        ck, cv = map(filled, llama.init_cache(cfg, S, C, kv_dtype,
                                              page_size=pg))
        spec5 = (None, None, None, "tp", None)
        mck, mcv = (kvcache.device_put(c, mesh, spec5) for c in (ck, cv))
        want = "pallas:paged_decode" + ("_int8" if kv_dtype == jnp.int8
                                        else "")
        assert llama.decode_attn_impl(mesh_cfg, ck) == want
        with pltpu.force_tpu_interpret_mode():
            got = decode(mesh_cfg)(sharded, mck, mcv)
        np.testing.assert_allclose(got, decode(jnp_cfg)(params, ck, cv),
                                   atol=2e-5)
        if kv_dtype == jnp.float32:
            assert llama.ragged_attn_impl(mesh_cfg, ck, N, True) == \
                "pallas:ragged_prefill"
            with pltpu.force_tpu_interpret_mode():
                got = prefill(mesh_cfg)(sharded, mck, mcv)
            np.testing.assert_allclose(
                got, prefill(jnp_cfg)(params, ck, cv), atol=2e-5)
