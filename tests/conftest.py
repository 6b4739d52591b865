"""Test bootstrap: an 8-device virtual CPU mesh, set up BEFORE jax initializes.

Mirrors the reference's test stance (SURVEY.md section 4) but adds what it
lacks: hermetic multi-device sharding tests without real hardware.

The platform and device count go into the ENVIRONMENT, not just this
process's jax.config, so every backend a test spawns (the model manager
passes its environment through) lands on the same CPU mesh — and so the
runner's "TPU or an explicit cpu" rule (backend/runner.py) is met.
"""

import os

os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"

import jax  # noqa: E402

# Persistent compilation cache: the suite builds dozens of Engine
# instances over the same tiny-llama shapes; deserializing repeat
# programs instead of recompiling keeps the whole tier-1 run inside
# its wall-clock budget (same helper the serving path uses).
from localai_tpu.utils.jaxtools import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "e2e: full-stack tests spawning real backend subprocesses")
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 "
                   "verify budget (ROADMAP runs -m 'not slow')")


@pytest.fixture(scope="session")
def tiny_llama():
    """A tiny randomly-initialized llama for engine/API tests."""
    from localai_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        max_position_embeddings=128,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


class ByteTokenizer:
    """Minimal tokenizer for hermetic tests: bytes <-> ids, id 0 = EOS."""

    eos_token_id = 0
    bos_token_id = 1

    def encode(self, text: str):
        return [2 + b for b in text.encode("utf-8")]

    def decode(self, ids, skip_special_tokens=True):
        data = bytes(i - 2 for i in ids if i >= 2)
        return data.decode("utf-8", errors="replace")

    def get_vocab_size(self):
        return 258


@pytest.fixture(scope="session")
def byte_tokenizer():
    return ByteTokenizer()
