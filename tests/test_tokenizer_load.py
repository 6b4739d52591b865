"""The tokenizer stage of ``LoadModel`` (backend/runner.py::_read_tokenizer):
it runs on a thread of its own beside the weights and is joined where the
engine first takes the tokenizer, under ``load_tokenizer_join``."""

import os
import shutil
import threading

import pytest

from localai_tpu.backend import contract_pb2 as pb
from localai_tpu.backend import runner
from tests.tinymodel import write_tiny_checkpoint

TEXT = "the quick brown fox, 12 times"


@pytest.fixture(autouse=True)
def _no_precompile(monkeypatch):
    monkeypatch.setenv("LOCALAI_PRECOMPILE", "0")


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tok-ckpt"))
    write_tiny_checkpoint(d)
    return d


@pytest.fixture(scope="module")
def gguf_ckpt(tmp_path_factory):
    from tests.test_gguf import _tiny_gguf

    return _tiny_gguf(tmp_path_factory.mktemp("tok-gguf"))[0]


def _load(sv, model, **kw):
    return sv.LoadModel(pb.ModelOptions(
        model=model, dtype="float32", context_size=64, num_slots=2,
        prefill_buckets=[16], mesh_tp=1, **kw), None)


def _copy_of(ckpt, dst):
    shutil.copytree(ckpt, dst)
    return str(dst)


def _named(ring):
    return {s["name"]: s for s in ring.spans() if s["track"] == "load"}


def test_the_stage_runs_beside_the_weights_and_is_joined(ckpt):
    sv = runner.EngineServicer()
    try:
        res = _load(sv, ckpt)
        assert res.success, res.message
        spans = _named(sv.tracer)
        tok, join = spans["load_tokenizer"], spans["load_tokenizer_join"]
        assert tok["t0"] < spans["load_device_wait"]["t1"]
        # the join is the last of the stage the load sees, just before the
        # engine is built with what it handed over
        assert tok["t1"] <= join["t1"] <= spans["load_engine_init"]["t0"]
        assert spans["load_device_wait"]["t1"] <= join["t0"]
        by = sv.engine.state_snapshot()["trace"]["by_span_ms"]
        assert by["load_tokenizer"]["count"] == 1
        assert by["load_tokenizer_join"]["count"] == 1
        for s in (tok, join):
            assert 0 < s["args"]["rss_mb"] <= s["args"]["rss_peak_mb"]
    finally:
        sv.engine.shutdown()


@pytest.mark.parametrize("source", ["safetensors", "gguf", "gguf_named_dir"])
def test_every_source_takes_the_thread_while_the_weights_load(
        source, ckpt, gguf_ckpt, monkeypatch):
    """The weight load is held until the stage has run: a stage that came
    after the weights, as it used to, would leave it waiting."""
    import transformers

    from localai_tpu.engine import gguf_tokenizer, weights

    ran = {}
    done = threading.Event()

    def seen(kind, fn):
        def wrapper(*a, **kw):
            ran[kind] = threading.current_thread().name.rsplit("_", 1)[0]
            try:
                return fn(*a, **kw)
            finally:
                done.set()
        return wrapper

    def held(fn):
        def wrapper(*a, **kw):
            ran["overlapped"] = done.wait(timeout=120)
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(gguf_tokenizer, "from_gguf",
                        seen("gguf", gguf_tokenizer.from_gguf))
    monkeypatch.setattr(transformers.AutoTokenizer, "from_pretrained",
                        seen("hf", transformers.AutoTokenizer.from_pretrained))
    monkeypatch.setattr(weights, "load_llama_params",
                        held(weights.load_llama_params))
    sv = runner.EngineServicer()
    try:
        if source == "safetensors":
            res = _load(sv, ckpt)
        elif source == "gguf":
            res = _load(sv, gguf_ckpt)
        else:
            res = _load(sv, gguf_ckpt, tokenizer=ckpt)
        assert res.success, res.message
        want = "gguf" if source == "gguf" else "hf"
        assert ran == {want: "load-tokenizer", "overlapped": True}
        assert sv.engine.tokenizer is sv.tokenizer
        assert {"load_tokenizer", "load_tokenizer_join"} <= set(
            _named(sv.tracer))
    finally:
        sv.engine.shutdown()


def test_the_engine_is_handed_what_from_pretrained_gives(ckpt):
    from transformers import AutoTokenizer

    ref = AutoTokenizer.from_pretrained(ckpt)
    sv = runner.EngineServicer()
    try:
        assert _load(sv, ckpt).success
        got = sv.engine.tokenizer
        assert got is sv.tokenizer and type(got) is type(ref)
        ids = ref.encode(TEXT)
        assert got.encode(TEXT) == ids and len(ids) > 8
        assert got.decode(ids) == ref.decode(ids) == TEXT
        assert got.eos_token_id == ref.eos_token_id
    finally:
        sv.engine.shutdown()


def test_an_unreadable_tokenizer_fails_the_load_as_it_did(ckpt, tmp_path):
    from transformers import AutoTokenizer

    d = _copy_of(ckpt, tmp_path / "bad-tok")
    with open(os.path.join(d, "tokenizer.json"), "w") as f:
        f.write("{ not a tokenizer")
    with pytest.raises(Exception) as direct:
        AutoTokenizer.from_pretrained(d)
    sv = runner.EngineServicer()
    res = _load(sv, d)
    assert not res.success
    assert res.message == \
        f"{type(direct.value).__name__}: {direct.value}"
    assert sv.tokenizer is None and sv.engine is None
    # the join's span is there, and what it waited is on the record
    assert "load_tokenizer_join" in _named(sv.tracer)


def test_a_load_that_fails_at_the_weights_sets_no_tokenizer(ckpt, tmp_path):
    d = _copy_of(ckpt, tmp_path / "no-weights")
    os.remove(os.path.join(d, "model.safetensors"))
    sv = runner.EngineServicer()
    res = _load(sv, d)
    assert not res.success and "safetensors" in res.message
    assert sv.tokenizer is None and sv.engine is None
    assert "load_tokenizer_join" not in _named(sv.tracer)
    # the thread's work ends on its own and goes nowhere
    for t in threading.enumerate():
        if t.name.startswith("load-tokenizer"):
            t.join(timeout=120)
            assert not t.is_alive()
    assert sv.tokenizer is None


@pytest.mark.parametrize("preset,want", [(None, "0"), ("1", "1")])
def test_the_runner_process_asks_transformers_for_no_torch(
        preset, want, monkeypatch):
    """``main`` sets transformers' own switch for its process unless the
    operator has set it."""
    class _Server:
        def start(self):
            pass

        def wait_for_termination(self):
            pass

    monkeypatch.setattr(runner, "make_server", lambda *a, **kw: _Server())
    monkeypatch.setenv("USE_TORCH", preset or "unset")
    if preset is None:
        monkeypatch.delenv("USE_TORCH")
    runner.main(["--addr", "127.0.0.1:0"])
    assert os.environ["USE_TORCH"] == want


def test_without_torch_the_tokenizer_is_the_same_object(ckpt):
    """What ``USE_TORCH=0`` buys and what it leaves alone, with the
    transformers that is installed: no torch in the process, the same
    class, the same ids and text."""
    import json
    import subprocess
    import sys

    from transformers import AutoTokenizer

    ref = AutoTokenizer.from_pretrained(ckpt)
    code = (
        "import json, sys\n"
        "from transformers import AutoTokenizer\n"
        f"t = AutoTokenizer.from_pretrained({ckpt!r})\n"
        f"ids = t.encode({TEXT!r})\n"
        "print(json.dumps({'cls': type(t).__name__, 'ids': ids,\n"
        "    'text': t.decode(ids), 'eos': t.eos_token_id,\n"
        "    'torch': 'torch' in sys.modules}))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], check=True, capture_output=True,
        text=True, env={**os.environ, "USE_TORCH": "0"}, timeout=300)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    ids = ref.encode(TEXT)
    assert got == {"cls": type(ref).__name__, "ids": ids,
                   "text": ref.decode(ids), "eos": ref.eos_token_id,
                   "torch": False}
