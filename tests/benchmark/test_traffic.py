"""The traffic generators: every seed gives the same multiset of work."""

import collections
import json
import os

import pytest

from benchmark import spec, traffic_lib as tl
from benchmark.generators import doc_sessions

SEEDS = (1, 7, 2**31 + 12345)
CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]


def _schedule(name, seed, seconds=None):
    cell = spec.resolve(name)
    gen = spec.generator(cell.traffic["generator"])
    return gen(cell.traffic, seconds or cell.run_seconds, seed,
               cell.config["vocab_size"],
               int(cell.config["serving"]["context_size"])), cell


def _work(sched):
    return collections.Counter((r.prompt_tokens, r.max_tokens)
                               for r in sched.window)


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_gives_the_same_multiset_of_work(name):
    scheds = [_schedule(name, s)[0] for s in SEEDS]
    first = scheds[0]
    for other in scheds[1:]:
        assert len(other.window) == len(first.window)
        assert _work(other) == _work(first)
        assert sum(r.prompt_tokens for r in other.window) == \
            sum(r.prompt_tokens for r in first.window)
        assert sum(r.max_tokens for r in other.window) == \
            sum(r.max_tokens for r in first.window)
        assert len(other.warmup) == len(first.warmup)
        # ... and differs in order and in contents
        assert [r.prompt_ids for r in other.window] != \
            [r.prompt_ids for r in first.window]
        assert [(r.prompt_tokens, r.max_tokens) for r in other.window] != \
            [(r.prompt_tokens, r.max_tokens) for r in first.window]


@pytest.mark.parametrize("name", [c for c in CELLS if "sat" not in c])
def test_open_loop_gaps_are_the_same_multiset_and_sum_to_the_window(name):
    gaps = []
    for seed in SEEDS:
        sched, cell = _schedule(name, seed)
        due = [r.due for r in sched.window]
        assert due == sorted(due)
        assert 0.0 < due[0] and due[-1] < cell.run_seconds
        # a request is due half its own gap after the previous gap's end
        edges, t = [], 0.0
        for d in due:
            g = 2 * (d - t)
            edges.append(g)
            t += g
        assert t == pytest.approx(cell.run_seconds, rel=1e-9)
        gaps.append(sorted(round(g, 9) for g in edges))
        assert all(r.due < 0 for r in sched.warmup)
    assert gaps[0] == gaps[1] == gaps[2]


def test_same_seed_same_schedule():
    a, _ = _schedule(CELLS[0], 42)
    b, _ = _schedule(CELLS[0], 42)
    assert [(r.prompt_ids, r.max_tokens, r.due) for r in a.window] == \
        [(r.prompt_ids, r.max_tokens, r.due) for r in b.window]


@pytest.mark.parametrize("name", CELLS)
def test_every_request_fits_its_slot(name):
    sched, cell = _schedule(name, 3)
    ctx = int(cell.config["serving"]["context_size"])
    for r in sched.window + sched.warmup:
        assert r.prompt_tokens + r.max_tokens <= ctx - tl.CONTEXT_MARGIN
        assert min(r.prompt_ids) >= tl.FIRST_TOKEN_ID
        assert max(r.prompt_ids) < cell.config["vocab_size"]


def test_stratified_blocks_span_the_range():
    """Every block of ~16 carries short and long requests alike."""
    sched, _ = _schedule("mistral7b.chat_rate", 5)
    lens = [r.prompt_tokens for r in sched.window]
    lo, hi = sorted(lens)[len(lens) // 4], sorted(lens)[3 * len(lens) // 4]
    for i in range(0, len(lens) - 15, 15):
        block = lens[i:i + 15]
        assert min(block) <= lo and max(block) >= hi


def test_mid_quantiles_and_gaps():
    q = tl.mid_quantiles({"dist": "uniform", "min": 0, "max": 100}, 4)
    assert q == [12, 38, 62, 88]            # (i + 0.5) / 4 of the range, rounded
    ln = tl.mid_quantiles({"dist": "lognormal", "median": 192, "sigma": 0.8,
                           "min": 32, "max": 768}, 101)
    assert ln[50] == 192 and ln[0] == 32 and ln[-1] == 768
    assert ln == sorted(ln)
    g = tl.exponential_gaps(50, 20.0)
    assert sum(g) == pytest.approx(20.0) and g == sorted(g)
    with pytest.raises(ValueError):
        tl.mid_quantiles({"dist": "zipf", "min": 1, "max": 2}, 3)


def test_doc_sessions_spacing_and_sharing():
    sched, cell = _schedule("nemo12b.docqa_rate", 9)
    asks = cell.traffic["asks_per_doc"]
    by_doc = collections.defaultdict(list)
    for k, r in enumerate(sched.window):
        by_doc[r.tag.split(".")[0]].append((k, r))
    assert len(sched.window) == asks * len(by_doc)
    for doc, items in by_doc.items():
        assert len(items) == asks
        pos = [k for k, _ in items]
        assert min(b - a for a, b in zip(pos, pos[1:])) >= asks
        # the asks of one document share the document and nothing after it
        n_doc = min(len(r.prompt_ids) for _, r in items) - 96
        heads = {tuple(r.prompt_ids[:n_doc]) for _, r in items}
        assert len(heads) == 1
        assert len({tuple(r.prompt_ids) for _, r in items}) == asks
        assert [r.tag for _, r in items] == \
            [f"{doc}.ask{i}" for i in range(asks)]
    # no document of the warm-up appears in the window
    warm = {tuple(r.prompt_ids[:1024]) for r in sched.warmup}
    assert not warm & {tuple(r.prompt_ids[:1024]) for r in sched.window}


@pytest.mark.parametrize("n_docs", [1, 2, 3, 4, 5, 6, 13, 14])
def test_doc_positions_ask_every_document_the_stated_times(n_docs):
    seq = doc_sessions.positions(n_docs, 4, 5)
    assert sorted(collections.Counter(seq).items()) == \
        [(j, 4) for j in range(n_docs)]
    assert doc_sessions.min_spacing(seq) >= min(4, n_docs)


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.resolve("no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.generator("no_such_generator")
    with pytest.raises(spec.SpecError):
        spec.layer_reader("no_such_metric")


def test_closed_loop_list_is_the_same_work_in_another_order():
    """chat_sat is kept as a mix (PERF.md section 7); its generator is
    held to the same rule as the open-loop ones."""
    with open(os.path.join(spec.ROOT, "benchmark", "traffic",
                           "chat_sat.json")) as f:
        params = json.load(f)
    gen = spec.generator(params["generator"])
    a, b = (gen(params, 51, s, 32768, 1024) for s in (3, 2**31 + 4))
    assert a.mode == "closed" and a.clients == 32 and not a.warmup
    assert _work(a) == _work(b) and len(a.window) >= 32
    assert [r.prompt_ids for r in a.window] != [r.prompt_ids for r in b.window]
    assert all(r.due is None for r in a.window)
