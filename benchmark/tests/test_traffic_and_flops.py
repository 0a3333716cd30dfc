"""The request generator and the FLOP and byte functions."""
import json
import os
from collections import Counter

import pytest

from benchmark.harness import flops, stats, traffic as T
from benchmark.harness.manifest import ROOT


def _traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def test_same_seed_same_requests_other_seed_same_sizes():
    t = dict(_traffic("offline-closed"), kind="serve_open", rate_rps=8.0,
             arrivals="poisson")
    big = 2 ** 31 + 12345
    a, b = T.open_loop(t, 50257, big, 30), T.open_loop(t, 50257, big, 30)
    assert a == b
    c = T.open_loop(t, 50257, 7, 30)
    assert a != c
    sizes = lambda rs: Counter((len(r["payload"]["prompt"]),) for r in rs)
    outs = lambda rs: Counter(r["payload"]["max_tokens"] for r in rs)
    assert sizes(a) == sizes(c) and outs(a) == outs(c)
    gaps = lambda rs: sorted(round(y - x, 9) for x, y in zip(
        [0.0] + [r["due_s"] for r in rs], [r["due_s"] for r in rs]))
    assert len(a) == round(t["rate_rps"] * 30) and a[-1]["due_s"] < 30
    assert sum(r["greedy"] for r in a) == round(0.8 * len(a))
    for r in a:
        p, o = len(r["payload"]["prompt"]), r["payload"]["max_tokens"]
        assert 16 <= p <= 768 and 8 <= o <= 256 and p + o <= 1024
        assert all(0 <= tok < 50257 for tok in r["payload"]["prompt"])
    assert gaps(a) == pytest.approx(gaps(c), abs=1e-8)
    med = stats.median([len(r["payload"]["prompt"]) for r in a])
    assert 240 <= med <= 272


def test_closed_pool_and_buckets():
    t = _traffic("offline-closed")
    pool = T.closed_loop(t, 50257, 3)
    assert len(pool) == t["pool"] and t["pool"] >= 4 * t["clients"]
    assert T.prefill_buckets(t) == [16, 32, 64, 128, 256, 512, 1024]


def test_the_seed_pairs_and_orders_the_pool_stratified_in_time():
    """The same lengths for every seed; the seed decides which prompt goes
    with which output and in what order they come; every ``order_block``
    consecutive requests hold one length from each stratum, so any part of
    the pool a window sees carries the file's mix."""
    t = {k: v for k, v in _traffic("offline-closed").items()
         if k != "order_seed"}
    n, block = t["pool"], t["order_block"]
    a = T.closed_loop(t, 50257, 2 ** 31 + 5)
    b = T.closed_loop(t, 50257, 11)
    size = lambda r: (r["size"]["prompt_len"], r["size"]["max_tokens"])
    assert sorted(p for p, _ in map(size, a)) == \
        sorted(p for p, _ in map(size, b))
    assert sorted(o for _, o in map(size, a)) == \
        sorted(o for _, o in map(size, b))
    assert sorted(map(size, a)) != sorted(map(size, b))      # pairing
    assert [size(r) for r in a] != [size(r) for r in b]      # order
    prompts = sorted(r["size"]["prompt_len"] for r in a)
    for pool in (a, b):
        totals = []
        for k in range(0, n, block):
            part = pool[k:k + block]
            ranks = sorted(prompts.index(r["size"]["prompt_len"])
                           // (n // block) for r in part)
            # ties between equal lengths may shift a rank by one stratum
            assert all(abs(x - y) <= 1 for x, y in zip(ranks, range(block)))
            totals.append(sum(r["size"]["prompt_len"]
                              + r["size"]["max_tokens"] for r in part))
        assert max(totals) - min(totals) < 0.03 * min(totals)
        assert 18 <= sum(r["greedy"] for r in pool[:block]) <= 20


def test_order_seed_gives_every_seed_the_same_sizes_in_the_same_order():
    """``offline-closed``'s window ends before its pool does, so the order
    is the amount of work: with ``order_seed`` it is the file's, and
    ``--seed`` draws only the token ids and the sampling seeds."""
    t = _traffic("offline-closed")
    assert "order_seed" in t
    a = T.closed_loop(t, 50257, 2 ** 31 + 5)
    b = T.closed_loop(t, 50257, 11)
    assert [r["size"] for r in a] == [r["size"] for r in b]
    assert [r["greedy"] for r in a] == [r["greedy"] for r in b]
    assert all(x["payload"]["prompt"] != y["payload"]["prompt"]
               and x["payload"]["seed"] != y["payload"]["seed"]
               for x, y in zip(a, b))
    assert a == T.closed_loop(t, 50257, 2 ** 31 + 5)
    other = T.closed_loop(dict(t, order_seed=24), 50257, 11)
    assert [r["size"] for r in other] != [r["size"] for r in b]
    assert sorted(r["size"]["prompt_len"] for r in other) == \
        sorted(r["size"]["prompt_len"] for r in b)


def test_flop_convention():
    # GPT-2 124M at T=1024: 0.798 GFLOP per trained token
    per_token = flops.train_flops_per_token(12, 768, 3072, 50257, 1024)
    matmul = 12 * (4 * 768 ** 2 + 2 * 768 * 3072) + 50257 * 768
    assert per_token == 6 * matmul + 6 * 12 * 768 * 1024
    assert per_token == pytest.approx(0.798e9, rel=2e-3)
    # GPT-2 large: 4.92 GFLOP
    assert flops.train_flops_per_token(36, 1280, 5120, 50257, 1024) == \
        pytest.approx(4.92e9, rel=2e-3)
    # the attention part of the step's count is the kernels' count
    need = flops.flash_causal_train(32, 12, 1024, 64, 12)
    assert need["flops"] == pytest.approx(32 * 1024 * 6 * 12 * 768 * 1024)
    assert need["bytes"] == 12 * 12 * 32 * 12 * 1024 * 64 * 2
    share, bound = flops.roofline_share_pct(197e12, 1e9, 2.0, 197e12, 819e9)
    assert share == pytest.approx(50.0) and bound == "compute"


def test_percentile_and_spread():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0
