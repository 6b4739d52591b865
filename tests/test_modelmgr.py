"""Model lifecycle tests against the fake backend (spawned + embedded)."""

import os
import signal
import socket
import time

import grpc
import pytest

from localai_tpu.backend import contract_pb2 as pb
from localai_tpu.backend.fake import FakeServicer
from localai_tpu.modelmgr import process as process_mod
from localai_tpu.modelmgr.loader import ModelLoader
from localai_tpu.modelmgr.watchdog import WatchDog
from localai_tpu.services.errors import CircuitOpenError
from localai_tpu.services.faults import FAULTS


@pytest.fixture()
def loader():
    ml = ModelLoader(health_attempts=60, health_interval_s=0.2)
    yield ml
    ml.stop_all()


def test_embedded_backend_load_and_predict(loader):
    loader.register_embedded("fake", FakeServicer)
    lm = loader.backend_loader("fake", "m1", pb.ModelOptions(model="whatever"))
    assert lm.client.health()
    r = lm.client.predict(pb.PredictOptions(prompt="hello world"))
    assert r.message == b"hello world"
    assert r.finish_reason == "stop"


def test_spawned_backend_process(loader):
    lm = loader.backend_loader("fake", "m2", pb.ModelOptions(model="x"))
    assert lm.process is not None and lm.process.alive()
    chunks = list(lm.client.predict_stream(pb.PredictOptions(prompt="a b c")))
    assert b"".join(c.message for c in chunks) == b"a b c"
    assert chunks[-1].finish_reason == "stop"
    loader.shutdown_model("m2")
    assert loader.get("m2") is None


def test_load_failure_surfaces(loader):
    loader.register_embedded("fake", FakeServicer)
    with pytest.raises(RuntimeError, match="fake load failure"):
        loader.backend_loader("fake", "bad", pb.ModelOptions(model="fail-this"))


def test_dead_backend_error_carries_its_stderr(loader):
    """A backend that dies before it is ready (the chip is taken, a
    kernel does not compile, an import breaks) must say why in the error
    the caller gets — its output is only logged at DEBUG."""
    loader.register_external("doomed", "localai_tpu.no_such_backend")
    with pytest.raises(RuntimeError) as ei:
        loader.backend_loader("doomed", "d1", pb.ModelOptions(model="x"))
    msg = str(ei.value)
    assert "died during startup" in msg and "exit 1" in msg
    assert "No module named" in msg and "no_such_backend" in msg


def test_spawned_backend_imports_from_any_cwd(loader, tmp_path, monkeypatch):
    """The spawner puts the package root on the child's PYTHONPATH: the
    server no longer has to be started from the checkout."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("PYTHONPATH", raising=False)
    lm = loader.backend_loader("fake", "cwd1", pb.ModelOptions(model="x"))
    assert lm.process.alive() and lm.client.health()


def test_model_reuse_same_client(loader):
    loader.register_embedded("fake", FakeServicer)
    a = loader.backend_loader("fake", "m3", pb.ModelOptions(model="x"))
    b = loader.backend_loader("fake", "m3", pb.ModelOptions(model="x"))
    assert a is b


def test_respawn_after_process_death(loader):
    lm = loader.backend_loader("fake", "m4", pb.ModelOptions(model="x"))
    lm.process.stop()
    lm2 = loader.backend_loader("fake", "m4", pb.ModelOptions(model="x"))
    assert lm2 is not lm
    assert lm2.client.health()


def test_greedy_loader_falls_through(loader):
    calls = []

    class Failing(FakeServicer):
        def LoadModel(self, request, context):
            calls.append("failing")
            return pb.Result(success=False, message="nope")

    loader.register_embedded("bad", Failing)
    loader.register_embedded("good", FakeServicer)
    lm = loader.greedy_loader("m5", pb.ModelOptions(model="x"), order=["bad", "good"])
    assert lm.backend_name == "good"
    assert calls == ["failing"]


def test_stores_roundtrip_via_contract(loader):
    loader.register_embedded("fake", FakeServicer)
    lm = loader.backend_loader("fake", "st", pb.ModelOptions(model="x"))
    lm.client.stores_set(pb.StoresSetOptions(
        keys=[pb.StoresKey(floats=[1.0, 0.0]), pb.StoresKey(floats=[0.0, 1.0])],
        values=[pb.StoresValue(bytes=b"a"), pb.StoresValue(bytes=b"b")],
    ))
    found = lm.client.stores_find(pb.StoresFindOptions(
        key=pb.StoresKey(floats=[1.0, 0.1]), top_k=1))
    assert found.values[0].bytes == b"a"
    assert found.similarities[0] > 0.9


# ---- fault-tolerant lifecycle (ISSUE 7) ----


def _poll(predicate, timeout_s=10.0, step_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(step_s)
    return predicate()


def test_watchdog_kills_busy_too_long(loader):
    loader.register_embedded("fake", FakeServicer)
    wd = WatchDog(loader, busy_timeout_s=0.05, check_busy=True,
                  sweep_interval_s=0.05)
    loader.watchdog = wd
    wd.start()
    try:
        lm = loader.backend_loader("fake", "wd1", pb.ModelOptions(model="x"))
        lm.mark_busy()  # never marked idle: a wedged request
        assert _poll(lambda: loader.get("wd1") is None)
    finally:
        wd.shutdown()


def test_watchdog_releases_idle(loader):
    loader.register_embedded("fake", FakeServicer)
    wd = WatchDog(loader, idle_timeout_s=0.05, check_idle=True,
                  sweep_interval_s=0.05)
    loader.watchdog = wd
    wd.start()
    try:
        loader.backend_loader("fake", "wd2", pb.ModelOptions(model="x"))
        assert _poll(lambda: loader.get("wd2") is None)
    finally:
        wd.shutdown()


def test_health_probe_grace_keeps_live_backend(loader):
    """A transiently failing probe must NOT kill a live backend: 3
    strikes spread over 30 s are required before a respawn."""

    class Flaky(FakeServicer):
        fail = False

        def Health(self, request, context):
            if Flaky.fail:
                context.abort(grpc.StatusCode.UNAVAILABLE, "probe fail")
            return super().Health(request, context)

    Flaky.fail = False
    loader.register_embedded("flaky", Flaky)
    lm = loader.backend_loader("flaky", "m6", pb.ModelOptions(model="x"))
    Flaky.fail = True
    a = loader.backend_loader("flaky", "m6", pb.ModelOptions(model="x"))
    b = loader.backend_loader("flaky", "m6", pb.ModelOptions(model="x"))
    assert a is lm and b is lm
    assert lm.health_fails >= 2
    Flaky.fail = False
    c = loader.backend_loader("flaky", "m6", pb.ModelOptions(model="x"))
    assert c is lm and lm.health_fails == 0


def test_supervisor_respawns_killed_backend():
    ml = ModelLoader(health_attempts=60, health_interval_s=0.2,
                     respawn_backoff_base_s=0.05,
                     respawn_backoff_cap_s=0.2)
    try:
        lm = ml.backend_loader("fake", "sup1", pb.ModelOptions(model="x"))
        assert lm.process is not None and lm.process.alive()
        os.kill(lm.process.proc.pid, signal.SIGKILL)

        def replaced():
            cur = ml.get("sup1")
            return (cur is not None and cur is not lm
                    and cur.client.health(timeout=1.0))

        assert _poll(replaced, timeout_s=30.0, step_s=0.05)
        assert ml.stats()["sup1"]["respawns"] >= 1
        assert ml.stats()["sup1"]["breaker"]["state"] == "closed"
    finally:
        ml.stop_all()


def test_circuit_breaker_opens_then_recovers():
    ml = ModelLoader(breaker_threshold=2, breaker_cooldown_s=0.3)
    ml.register_embedded("fake", FakeServicer)
    try:
        for _ in range(2):
            with pytest.raises(RuntimeError, match="fake load failure"):
                ml.backend_loader("fake", "cb1",
                                  pb.ModelOptions(model="fail-this"))
        t0 = time.monotonic()
        with pytest.raises(CircuitOpenError) as ei:
            ml.backend_loader("fake", "cb1",
                              pb.ModelOptions(model="fail-this"))
        assert time.monotonic() - t0 < 0.1  # fast-fail: no spawn attempt
        assert ei.value.status == 503
        assert ei.value.retryable
        assert ei.value.detail["breaker"]["state"] == "open"
        assert ei.value.retry_after_s >= 1.0
        assert ml.stats()["cb1"]["circuit_state"] == 1
        time.sleep(0.35)
        # half-open probe with a now-working config closes the breaker
        lm = ml.backend_loader("fake", "cb1", pb.ModelOptions(model="ok"))
        assert lm.client.health()
        assert ml.stats()["cb1"]["breaker"]["state"] == "closed"
    finally:
        ml.stop_all()


def test_spawn_retries_lost_bind_race(monkeypatch):
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    stolen = blocker.getsockname()[1]
    real_free_port = process_mod.free_port
    ports = [stolen]

    def rigged_free_port():
        return ports.pop(0) if ports else real_free_port()

    monkeypatch.setattr(process_mod, "free_port", rigged_free_port)
    bp = process_mod.spawn_python_backend(
        "localai_tpu.backend.fake", name="race", bind_race_wait_s=15.0)
    try:
        assert bp.addr != f"127.0.0.1:{stolen}"
        assert _poll(bp.started.is_set, timeout_s=20.0, step_s=0.05)
    finally:
        bp.stop(grace_s=0.0)
        blocker.close()


def test_unary_retry_absorbs_injected_unavailable(loader):
    loader.register_embedded("fake", FakeServicer)
    lm = loader.backend_loader("fake", "rt1", pb.ModelOptions(model="x"))
    FAULTS.arm("rpc_unavailable", "Embedding", count=2)
    try:
        res = lm.client.embedding(pb.PredictOptions(prompt="hi"))
        assert list(res.embeddings)
        assert FAULTS.fired.get("rpc_unavailable") == 2
    finally:
        FAULTS.reset()
