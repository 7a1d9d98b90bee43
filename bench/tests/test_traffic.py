"""The traffic generator: deterministic per seed, and every seed gets the
same work in another order."""
import json
import os

import numpy as np
import pytest

from bench.lib import traffic

MIXES = ["chat", "batch"]


def draw(name, seed, horizon=30.0):
    return traffic.requests(traffic.load(name), seed, vocab=151936,
                            horizon_s=horizon)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a, b = draw(name, 2**31 + 77), draw(name, 2**31 + 77)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["due_s"] == y["due_s"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("name", MIXES)
def test_other_seed_same_work_other_order(name):
    a = [r for r in draw(name, 1) if r["due_s"] >= 0]
    b = [r for r in draw(name, 2) if r["due_s"] >= 0]
    k = traffic.load(name)["block"]
    n = min(len(a), len(b)) // k * k
    # lengths as drawn: a request part-way through its output carries the
    # served part in its prompt and asks for the rest
    la = sorted(len(r["prompt"]) - r["progress"] for r in a[:n])
    lb = sorted(len(r["prompt"]) - r["progress"] for r in b[:n])
    assert la == lb
    pa = sorted((r["max_new"] + r["progress"], r["progress"]) for r in a[:n])
    pb = sorted((r["max_new"] + r["progress"], r["progress"]) for r in b[:n])
    assert pa == pb
    assert [len(r["prompt"]) for r in a[:n]] != [len(r["prompt"])
                                                 for r in b[:n]]


def test_open_loop_arrivals():
    mix = traffic.load("chat")
    reqs = draw("chat", 5, horizon=20.0)
    due = np.array([r["due_s"] for r in reqs])
    assert np.all(np.diff(due) > 0)
    assert due[0] == -mix["lead_in_s"] and due[-1] < 20.0
    # one block of gaps spans k / rate seconds on average, on every seed
    k, rate = mix["block"], mix["rate_per_s"]
    gaps = traffic.exp_gap_grid(rate, k)
    assert gaps.sum() == pytest.approx(k / rate, rel=1e-12)
    # a lead-in of whole gap blocks opens the window on a block boundary
    kg = mix.get("gap_block", k)
    if mix["lead_in_s"] * rate == kg:
        assert sum(1 for r in reqs if r["due_s"] < 0) == kg
        assert any(r["due_s"] == 0.0 for r in reqs)


def test_lengths_within_bounds():
    mix = traffic.load("chat")
    reqs = draw("chat", 3)
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["max_new"] for r in reqs])
    assert p.min() >= mix["prompt_tokens"]["min"]
    assert p.max() <= mix["prompt_tokens"]["max"]
    assert o.min() >= mix["output_tokens"]["min"]
    assert o.max() <= mix["output_tokens"]["max"]
    assert abs(np.median(p) - mix["prompt_tokens"]["median"]) < 0.1 * \
        mix["prompt_tokens"]["median"]


def test_backlog_all_due_at_once():
    reqs = draw("batch", 9)
    assert len(reqs) == traffic.load("batch")["backlog"]
    assert all(r["due_s"] == 0.0 for r in reqs)


def test_backlog_in_flight_progress():
    """The batch the window opens on is part-way through its outputs: the
    in-flight requests' progress spreads over their outputs, the same
    total on every seed; the rest of the backlog starts from nothing."""
    mix = traffic.load("batch")
    m = mix["in_flight"]
    for seed in (9, 2**31 + 5):
        reqs = draw("batch", seed)
        prog = np.array([r["progress"] for r in reqs[:m]])
        out = np.array([r["max_new"] + r["progress"] for r in reqs[:m]])
        assert all(r["progress"] == 0 for r in reqs[m:])
        assert np.all(prog < out) and np.all(prog >= 0)
        share = prog / out
        assert 0.4 < share.mean() < 0.6 and share.min() < 0.05
        assert share.max() > 0.95
        total = prog.sum() if seed == 9 else total
        assert prog.sum() == total
        ctx = [len(r["prompt"]) + r["max_new"] for r in reqs]
        assert max(ctx) <= (mix["prompt_tokens"]["max"]
                            + mix["output_tokens"]["max"])


def test_paired_mix_same_requests_other_order():
    """A paired mix pairs each prompt quantile with one output quantile on
    every seed: whole blocks hold the same (prompt, output, progress)
    triples, so the in-flight batch reserves the same pages on every seed."""
    mix = traffic.load("batch")
    assert mix["paired"] and mix["in_flight"] % mix["block"] == 0

    def triples(seed):
        return [(len(r["prompt"]) - r["progress"], r["max_new"] + r["progress"],
                 r["progress"]) for r in draw("batch", seed)]

    a, b = triples(1), triples(2**31 + 11)
    n = len(a) // mix["block"] * mix["block"]
    assert sorted(a[:n]) == sorted(b[:n]) and a[:n] != b[:n]
    m = mix["in_flight"]
    assert sorted(a[:m]) == sorted(b[:m])
    # the pairing is not the identity: prompt and output lengths are not
    # ranked alike
    p = np.array([t[0] for t in a[:m]])
    o = np.array([t[1] for t in a[:m]])
    assert abs(np.corrcoef(p, o)[0, 1]) < 0.3


def test_batch_in_flight_fits_the_pool():
    """Every in-flight request of the batch mix gets its slot on every
    seed: their lifetime page reservations fit the qwen2 pool with room
    left for the largest request of the backlog, so which seed's order
    comes first never leaves a slot empty."""
    from repro.serve.engine import chunk_buckets_for, chunk_plan
    with open(os.path.join(os.path.dirname(traffic.TRAFFIC_DIR), "configs",
                           "qwen2-1.5b.json")) as f:
        srv = json.load(f)["serving"]
    page, chunk = srv["page_size"], srv["prefill_chunk"]
    buckets = chunk_buckets_for(chunk, page)

    def pages(r):
        n = len(r["prompt"])
        last = chunk_plan(n, chunk, buckets)[-1]
        return -(-max(last[0] + last[1], n + r["max_new"]) // page)

    mix = traffic.load("batch")
    reqs = draw("batch", 2**31 + 3)
    held = sum(pages(r) for r in reqs[:mix["in_flight"]])
    largest = max(pages(r) for r in reqs)
    assert mix["in_flight"] == mix["slots"]
    assert held + largest <= srv["num_pages"] - 1
