"""The generator offers the same work whatever the seed: the same count and
the same prompt and output tokens, in another order and at other instants."""
import collections

import pytest

from benchmarks.lib import traffic as T
from benchmarks.lib.configs import load_json

SEEDS = [0, 1, 7, 2**31 + 12345, 2**32 + 5]


@pytest.mark.parametrize("seconds", [20, 51])
def test_open_loop_offers_the_same_work_for_any_seed(seconds):
    tf = load_json("traffic", "chat_open.json")
    runs = [T.open_schedule(tf, s, seconds) for s in SEEDS]
    offered = [T.offered(r) for r in runs]
    assert all(o == offered[0] for o in offered)
    assert offered[0]["requests"] == round(tf["rate_rps"] * seconds)
    multisets = [collections.Counter((r.prompt_len, ) for r in run if r.sampled)
                 for run in runs]
    assert all(m == multisets[0] for m in multisets)
    orders = {tuple((r.prompt_len, r.max_tokens) for r in run if r.sampled)
              for run in runs}
    assert len(orders) == len(SEEDS), "the seed must change the order"
    dues = {tuple(r.due_s for r in run if r.sampled) for run in runs}
    assert len(dues) == len(SEEDS), "the seed must change the arrivals"


def test_open_loop_schedule_is_sorted_and_within_limits():
    tf = load_json("traffic", "chat_open.json")
    run = T.open_schedule(tf, 3, 51)
    assert [r.index for r in run] == list(range(len(run)))
    assert all(a.due_s <= b.due_s for a, b in zip(run, run[1:]))
    assert all(0 <= r.due_s < 51 for r in run if r.sampled)
    assert any(r.due_s < 0 for r in run) and any(r.due_s >= 51 for r in run)
    for r in run:
        assert r.prompt_len + r.max_tokens <= tf["max_total"]
        assert r.prompt_len in tf["prompt"]["lengths"]
        assert tf["output"]["min"] <= r.max_tokens <= tf["output"]["max"]


def test_same_seed_same_inputs():
    tf = load_json("traffic", "chat_open.json")
    assert T.open_schedule(tf, 2**31 + 9, 51) == T.open_schedule(tf, 2**31 + 9, 51)
    assert T.prompt_tokens(2**31 + 9, 4, 64, 32768) == T.prompt_tokens(
        2**31 + 9, 4, 64, 32768)
    toks = T.prompt_tokens(5, 0, 4096, 32768)
    assert min(toks) >= 3 and max(toks) < 32768


def test_closed_loop_list_is_one_multiset_permuted():
    tb = load_json("traffic", "batch_closed.json")
    lists = [T.closed_list(tb, s) for s in SEEDS]
    for name, col in (("prompts", 0), ("outputs", 1)):
        sets = [collections.Counter(p[col] for p in lst) for lst in lists]
        assert all(s == sets[0] for s in sets), name
    assert len({tuple(lst) for lst in lists}) == len(SEEDS)
    assert all(p + o <= tb["max_total"] for lst in lists for p, o in lst)


def test_quantiles_follow_the_distribution():
    spec = {"dist": "lognormal", "median": 400, "sigma": 0.8, "min": 1, "max": 10**6}
    xs = T.quantile_lengths(spec, 1001)
    assert xs == sorted(xs) and abs(xs[500] - 400) <= 1
    assert T.quantile_lengths({"dist": "uniform", "min": 16, "max": 64}, 3) == [24, 40, 56]
