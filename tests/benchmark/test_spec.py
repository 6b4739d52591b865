"""BENCHMARK.json against the contract's letter, and the files it names."""

import json
import os
import re

import pytest

from benchmark import make_checkpoint, roofline, spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[0-9A-Za-z_][0-9A-Za-z_.\-]{0,63}$")
UNIT = re.compile(r"^[0-9A-Za-z_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_KEYS = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$"
                        r"|_rank$|head_dim|expand|experts_per_tok")


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert len(BENCH["command"]) <= 32
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(metric):
    e2e = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
        # every cell that reads this metric reports what it should move
        assert set(metric.get("workloads", cells)) <= \
            set(moved.get("workloads", cells))
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
        spec.layer_reader(metric["name"])          # its reader exists


def test_names_are_unique_and_setup_is_there():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_workload_resolves_to_files_that_exist(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    cell = spec.resolve(w["name"])
    spec.generator(cell.traffic["generator"])
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    assert cell.traffic["check_lengths"]


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entries_and_files(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    assert c["source"].startswith("https://")
    assert not any(WIDTH_KEYS.search(k) for k in c["reduced"])
    with open(os.path.join(spec.ROOT, c["file"])) as f:
        conf = json.load(f)
    assert conf["source"] == c["source"]
    assert set(conf["reduced"]) == set(c["reduced"])
    assert {"serving", "precision", "check", "assumed"} <= set(conf)
    assert conf["check"]["limits"].keys() == {"logits_err", "kv_err", "v0_err"}
    assert "LOCALAI_ALLOW_RANDOM_WEIGHTS" not in json.dumps(conf)


# hand-worked: Mistral-7B widths, 12 layers; Nemo widths, 8 layers
HAND = {
    "mistral-7b-v0.3-l12": dict(
        per_layer=4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336,
        total=2_885_783_552, kv_token=2 * 12 * 8 * 128 * 2),
    "mistral-nemo-12b-l6": dict(
        per_layer=5120 * 4096 * 2 + 2 * 5120 * 1024 + 3 * 5120 * 14336,
        total=2_978_022_400, kv_token=2 * 6 * 8 * 128 * 2),
}


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_roofline_counts_against_hand_worked_numbers(c):
    with open(os.path.join(spec.ROOT, c["file"])) as f:
        hf = json.load(f)
    hand = HAND[c["name"]]
    p = roofline.weight_param_counts(hf)
    L = hf["num_hidden_layers"]
    assert p["layers"] == L * hand["per_layer"]
    assert sum(p.values()) == hand["total"]
    # the checkpoint maker writes exactly those parameters
    made = sum(int.__mul__(*s) if len(s) == 2 else s[0]
               for _, s, _ in make_checkpoint.tensor_table(hf, L))
    assert made == hand["total"]
    assert roofline.kv_bytes_per_token(hf) == hand["kv_token"]
    wb = 1 if hf["precision"]["weights"] == "int8" else 2
    least = roofline.decode_step_least_bytes(hf, wb, 8000, 16)
    want = (L * hand["per_layer"] + hf["vocab_size"] * hf["hidden_size"]) * wb \
        + (2 * L + 1) * hf["hidden_size"] * 2 \
        + 16 * hf["hidden_size"] * wb + 8000 * hand["kv_token"]
    assert least == want
    flops = roofline.decode_step_least_flops(hf, 8000, 16)
    assert flops == 2 * 16 * (L * hand["per_layer"]
                              + hf["vocab_size"] * hf["hidden_size"]) \
        + 4 * L * 32 * 128 * 8000
    peak = roofline.peaks("TPU v5 lite")
    assert roofline.least_seconds(least, flops, peak) == \
        pytest.approx(max(least / 819e9, flops / 197e12))
    assert least / 819e9 > flops / 197e12       # a decode step is bytes-bound


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
