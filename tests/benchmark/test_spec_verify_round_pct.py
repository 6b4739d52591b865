"""The reader of ``spec_verify_round_pct`` on hand-made spans."""

import types

import pytest

from benchmark import spec


def _span(name, **args):
    return {"name": name, "t": 1.0, "dur_ms": 8.0, "args": args}


WITH_COUNTS = [
    _span("spec_round", mode="ngram", rounds=2, rounds_verified=1,
          rows_drafted=1, proposed=4, accepted=0),
    _span("spec_round", mode="ngram", rounds=2, rounds_verified=0,
          rows_drafted=0, proposed=0, accepted=0),
]
# what a program that verifies every round records: no such count
WITHOUT = _span("spec_round", mode="ngram", rounds=2, spec_slots=5,
                proposed=40, accepted=0)
OTHER = _span("decode_burst_device", steps=2, spec=True)


@pytest.mark.parametrize("spans,want", [
    (WITH_COUNTS + [OTHER], 25.0),          # 1 of 4 rounds verified
    ([WITH_COUNTS[0], WITHOUT, OTHER], 50.0),  # the span without: left out
    ([WITHOUT, OTHER], None),               # the parent: nothing to read
    ([], None),                             # a family that never speculates
], ids=["two_with_counts", "one_without", "none_with_counts", "no_spans"])
def test_reader_on_hand_made_spans(spans, want):
    read = spec.layer_reader("spec_verify_round_pct")
    assert read(types.SimpleNamespace(spans=spans)) == want


def test_metric_is_listed_for_the_cells_that_speculate():
    bench = spec.load_benchmark()
    (m,) = [m for m in bench["per_layer"]
            if m["name"] == "spec_verify_round_pct"]
    assert m["moves"] == "tpot_p85_ms" and m["layer"] == "model step"
    assert sorted(m["workloads"]) == [
        "mistral7b.chat_rate", "nemo12b.chat_rate", "nemo12b.docqa_rate"]
