"""The traffic generator: a seed fixes the inputs; seeds share the work."""
import collections

import numpy as np
import pytest

import bench_testkit as kit
import traffic_gen

CHAT = kit._json(kit.ROOT / "bench" / "traffic" / "chat.json")
CHURN = kit._json(kit.ROOT / "bench" / "traffic" / "churn.json")
SEEDS = [1, 2**31 + 11, 2**40 + 5]


@pytest.mark.parametrize("seed", SEEDS)
def test_requests_repeat_per_seed(seed):
    a = traffic_gen.requests(CHAT, seed, 10.0, 1000, 5.0)
    b = traffic_gen.requests(CHAT, seed, 10.0, 1000, 5.0)
    assert a == b
    assert sum(r.in_window for r in a) == round(CHAT["rate_per_s"] * 10.0)
    assert all(r.due_s < 10.0 for r in a if r.in_window)


def test_request_seeds_share_sizes_and_gaps_in_another_order():
    a, b = (traffic_gen.requests(CHAT, s, 10.0, 1000, 5.0) for s in (3, 4))
    a, b = ([r for r in x if r.in_window] for x in (a, b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in b)
    gaps = [np.sort(np.diff([r.due_s for r in x])) for x in (a, b)]
    assert np.allclose(gaps[0][1:], gaps[1][1:], atol=1e-9) or len(set(
        np.round(np.concatenate(gaps), 9))) < len(a) * 2
    lo, hi = CHAT["prompt_len"]["min"], CHAT["prompt_len"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)
    assert a[0].prompt != b[0].prompt


def test_prefill_buckets_cover_every_prompt():
    buckets = traffic_gen.warmup_prompt_lengths(CHAT)
    for r in traffic_gen.requests(CHAT, 9, 20.0, 1000, 0.0):
        n = len(r.prompt)
        assert any(b >= n and b // 2 < n for b in buckets), n


@pytest.mark.parametrize("seed", SEEDS)
def test_churn_repeats_per_seed(seed):
    a = traffic_gen.churn(CHURN, seed, 40.0, 0.0, 60.0)
    assert a == traffic_gen.churn(CHURN, seed, 40.0, 0.0, 60.0)
    kinds = collections.Counter(e.kind for e in a)
    n_work = sum(len(e.workloads) for e in a if e.kind == "arrive")
    assert kinds["depart"] == n_work
    assert kinds["compact"] == 1
    assert kinds["arrive"] == round(CHURN["arrival_rate_per_s"] * 40.0)


def test_churn_seeds_share_profiles_and_bursts():
    a, b = (traffic_gen.churn(CHURN, s, 40.0, 0.0, 60.0) for s in (5, 6))

    def prof(ev):
        return sorted(p for e in ev if e.kind == "arrive" for _, p in e.workloads)

    def bursts(ev):
        return sorted(len(e.workloads) for e in ev if e.kind == "arrive")

    assert prof(a) == prof(b) and bursts(a) == bursts(b)
    assert [e.kind for e in a] != [e.kind for e in b]


@pytest.mark.parametrize("seed", SEEDS)
def test_churn_compacts_the_snapshot_before_any_arrival(seed):
    ev = traffic_gen.churn(CHURN, seed, 40.0, 0.0, 60.0)
    assert ev[0].kind == "compact" and ev[0].due_s == 0.0
    assert ev[1].kind == "arrive" and ev[1].due_s == 0.0
