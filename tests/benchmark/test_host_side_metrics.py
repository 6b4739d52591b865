"""The six readers of PR 43 (``runner_rss_peak_gb``, ``runner_rss_warm_gb``,
``compile_unowned_s``, ``compiles_unowned_after_warmup``, ``late_dispatch_s``,
``burst_step_max_over_p50``) on hand-made ``ctx`` objects, the parent's shape
among them: a program with no such span or counter gives None."""

import os
import types

import pytest

from benchmark import spec

NEW = ("runner_rss_peak_gb", "runner_rss_warm_gb", "compile_unowned_s",
       "compiles_unowned_after_warmup", "late_dispatch_s",
       "burst_step_max_over_p50")
# the cells whose family tests pin no position in the per-layer list; the
# three hybrid cells' tests pin their family's metrics as the LAST entries
# (PERF.md section 7), so nothing can be appended for them
LLAMA_CELLS = ["mistral7b.chat_rate", "nemo12b.docqa_rate",
               "nemo12b.chat_rate", "mistral7b.longgen_rate"]
HYBRID_CELLS = ["olmo-hybrid.longgen_rate", "granite-h-micro.longgen_many",
                "lfm2-24b-a2b.longgen_wide"]


def _span(name, dur_ms=8.0, **args):
    return {"name": name, "t": 1.0, "dur_ms": dur_ms, "args": args}


def _burst(dur_ms, steps=16):
    return _span("decode_burst_device", dur_ms, steps=steps, slots=4)


# what the parent records: bursts, and sync_wait with no arguments
PARENT_SPANS = [_burst(450.0), _burst(460.0), _span("sync_wait", 400.0)]
PARENT_STATE = {"compiles": {"compile_seconds_total": 14.0,
                             "compiles_after_warmup": 0}}
HEALTHY = [_burst(448.0), _burst(450.0), _burst(452.0), _burst(56.0, 2),
           _span("sync_wait", 200.0, kind="decode_burst", steps=16)]
LATE = _span("late_dispatch", 2650.0, kind="decode_burst", steps=16, slots=35,
             expected_ms=230.0, overdue_ms=2420.0, majflt=0, minflt=3,
             nvcsw=2, nivcsw=0, proc_user_ms=12.0, proc_sys_ms=1800.0,
             rss_mb=-1487.2, rss_anon_mb=-1480.1, rss_file_mb=-7.1,
             gc_ms=0.0, since_compile_s=43.5)
STATE = {
    "compiles_process": {
        "compiles_total": 131, "compile_seconds_total": 16.5,
        "unowned_compiles": 42, "unowned_seconds": 2.25,
        "unowned_from_cache": 40, "unowned_after_warmup": 1, "warm": True,
        "unowned_by_thread": {"ThreadPoolExecutor-0_0": [2.25, 42]},
        "unowned_last": []},
    "host_memory": {
        "rss_bytes": 9_100_000_000, "rss_peak_bytes": 24_600_000_000,
        "rss_anon_bytes": 8_000_000_000, "rss_file_bytes": 1_100_000_000,
        "rss_shmem_bytes": 0,
        "at_warm": {"rss_bytes": 10_250_000_000,
                    "rss_peak_bytes": 24_600_000_000},
        "peak_in_load": {"bytes": 24_600_000_000, "span": "load_cast",
                         "leaf": "w_down"}},
    **PARENT_STATE}
# a runner on a system with no /proc: the record is there and empty
NO_PROC = {"host_memory": {"at_warm": {}, "peak_in_load": {}}}


def _read(name, spans=(), state=None):
    return spec.layer_reader(name)(types.SimpleNamespace(
        spans=list(spans), state_end=state))


@pytest.mark.parametrize("spans,want", [
    (HEALTHY, 28.25 / 28.0625),     # 452 / 16 over the median of ms a step
    (HEALTHY + [_burst(2450.0)], 2450.0 / 450.0),   # PR 40's stalled burst
    ([_burst(450.0)], 1.0),
    (PARENT_SPANS, 460.0 / 455.0),          # the parent has these spans too
    ([_span("decode_burst_device", 450.0, slots=4)], None),     # no steps
    ([], None),
], ids=["healthy", "one_stalled_burst", "one_burst", "parent", "no_steps",
        "no_spans"])
def test_burst_step_max_over_p50(spans, want):
    assert _read("burst_step_max_over_p50", spans) == pytest.approx(want)


@pytest.mark.parametrize("spans,want", [
    (HEALTHY, 0.0),                         # the record is there: no stall
    (HEALTHY + [LATE], 2.42),
    (HEALTHY + [LATE, dict(LATE, args=dict(LATE["args"], overdue_ms=580.0))],
     3.0),
    (PARENT_SPANS, None),                   # sync_wait says no kind: no record
    ([], None),
], ids=["healthy", "one_late", "two_late", "parent", "no_spans"])
def test_late_dispatch_s(spans, want):
    assert _read("late_dispatch_s", spans) == pytest.approx(want)


@pytest.mark.parametrize("name,state,want", [
    ("compiles_unowned_after_warmup", STATE, 1),
    ("compiles_unowned_after_warmup", PARENT_STATE, None),
    ("compiles_unowned_after_warmup", None, None),
    ("compile_unowned_s", STATE, 2.25),
    ("compile_unowned_s", PARENT_STATE, None),
    ("compile_unowned_s", None, None),
    ("runner_rss_peak_gb", STATE, 24.6),
    ("runner_rss_peak_gb", PARENT_STATE, None),
    ("runner_rss_peak_gb", NO_PROC, None),
    ("runner_rss_peak_gb", None, None),
    ("runner_rss_warm_gb", STATE, 10.25),
    ("runner_rss_warm_gb", PARENT_STATE, None),
    ("runner_rss_warm_gb", NO_PROC, None),
    ("runner_rss_warm_gb", None, None),
])
def test_state_counters(name, state, want):
    assert _read(name, state=state) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_each_entry_lists_the_four_llama_cells_and_has_its_reader(name):
    bench = spec.load_benchmark()
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert m["workloads"] == LLAMA_CELLS
    assert m["moves"] == ("setup_s" if name in NEW[:3] else "tpot_p85_ms")
    assert m["layer"] == ("device" if m["source"] == "program_span"
                          else "loader / runner")
    assert os.path.isfile(os.path.join(
        os.path.dirname(spec.__file__), "layer_metrics", name + ".py"))
    for cell in LLAMA_CELLS:
        names = [x["name"] for x in spec.resolve(cell).per_layer]
        assert [n for n in names if n in NEW] == list(NEW)


@pytest.mark.parametrize("cell", HYBRID_CELLS)
def test_the_hybrid_cells_lists_are_unchanged(cell):
    """Their family tests hold the family's metrics as the last entries:
    no new metric may land behind them."""
    names = [x["name"] for x in spec.resolve(cell).per_layer]
    assert not set(names) & set(NEW)
    bench = spec.load_benchmark()
    assert [m["name"] for m in bench["per_layer"][-len(NEW):]] == list(NEW)
