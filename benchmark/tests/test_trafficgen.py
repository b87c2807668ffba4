"""The traffic generator: deterministic per seed, the same sizes and
arrival gaps for every seed in another order, the drawn distributions
reported."""

import json
import os

import numpy as np
import pytest

from benchmark import trafficgen

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def load(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat"])
def test_open_schedule_same_work_every_seed(name):
    tr = load(name)
    a = trafficgen.open_schedule(tr, 30.0, 50000, 5)
    b = trafficgen.open_schedule(tr, 30.0, 50000, 5)
    c = trafficgen.open_schedule(tr, 30.0, 50000, 2 ** 31 + 17)
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert all((x["prompt"] == y["prompt"]).all() for x, y in zip(a, b))
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in c]
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, c))
    gaps = lambda rs: np.sort(np.diff([r["due"] for r in rs] + [30.0]))
    np.testing.assert_allclose(gaps(a), gaps(c), rtol=1e-9, atol=1e-12)
    assert a[0]["due"] == 0.0 and a[-1]["due"] < 30.0
    ahead = tr["in_flight_at_start"]
    assert len(a) == ahead + round(tr["arrival"]["rate_per_s"] * 30.0)
    assert [r["due"] for r in a[:ahead + 1]] == [0.0] * (ahead + 1)
    assert a[ahead + 1]["due"] > 0.0


def test_lengths_follow_the_file():
    """The drawn lengths have the source's published means (to the few
    percent that rounding and a finite draw leave), and a reply is cut
    only to the positions its prompt leaves of a slot."""
    tr = load("chat")
    cls = tr["classes"][0]
    reqs = trafficgen.open_schedule(tr, 2000.0, 50000, 1)
    d = trafficgen.describe(reqs)
    n = d["prompt_len"]["n"]
    assert abs(d["prompt_len"]["sum"] / n - cls["prompt_len"]["mean"]) \
        <= 0.03 * cls["prompt_len"]["mean"]
    assert abs(d["output_len"]["sum"] / n - cls["output_len"]["mean"]) \
        <= 0.03 * cls["output_len"]["mean"]
    assert d["prompt_len"]["min"] >= 1 and d["output_len"]["min"] >= 1
    assert all(len(r["prompt"]) + r["max_new"] <= tr["max_total"]
               for r in reqs)
    assert any(len(r["prompt"]) + r["max_new"] == tr["max_total"]
               for r in reqs)


def test_closed_pool_fits_a_slot():
    tr = load("docbatch")
    pool = trafficgen.requests(tr, tr["requests"], 50000, 3)
    assert len(pool) == tr["requests"]
    assert all(1024 <= len(r["prompt"]) <= 1920 for r in pool)
    assert all(len(r["prompt"]) + r["max_new"] <= 2048 for r in pool)
    assert all(r["prompt"].max() < 50000 for r in pool)
    # every stretch of eight holds one request of each stratum of length
    other = trafficgen.requests(tr, tr["requests"], 50000, 2 ** 31 + 9)
    assert sorted(r["max_new"] for r in pool) == sorted(
        r["max_new"] for r in other)
    assert [r["max_new"] for r in pool] != [r["max_new"] for r in other]
    bounds = sorted(r["max_new"] for r in pool)[::8] + [10 ** 9]
    for reqs in (pool, other):
        for i in range(0, 64, 8):
            strata = sorted(
                max(s for s in range(8) if r["max_new"] >= bounds[s])
                for r in reqs[i:i + 8])
            assert strata == list(range(8)), (i, strata)
        sums = [sum(r["max_new"] for r in reqs[i:i + 8])
                for i in range(0, 64, 8)]
        assert max(sums) - min(sums) <= 0.1 * min(sums)


def test_bursts_and_classes_need_only_data():
    tr = {"arrival": {"process": "poisson", "rate_per_s": 8.0, "burst": 4},
          "classes": [
              {"share": 3, "prompt_len": {"dist": "uniform", "min": 10,
                                          "max": 10},
               "output_len": {"dist": "uniform", "min": 2, "max": 2}},
              {"share": 1, "prompt_len": {"dist": "uniform", "min": 50,
                                          "max": 60},
               "output_len": {"dist": "uniform", "min": 3, "max": 3}}]}
    reqs = trafficgen.open_schedule(tr, 10.0, 100, 9)
    assert len(reqs) == 80
    dues = [r["due"] for r in reqs]
    assert all(len(set(dues[i:i + 4])) == 1 for i in range(0, 80, 4))
    assert sum(r["cls"] == 1 for r in reqs) == 20


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        trafficgen.quantile({"dist": "zipf"}, 0.5)


def lengths_hash(reqs):
    import hashlib

    return hashlib.sha256(json.dumps(
        [[len(r["prompt"]), r["max_new"]] for r in reqs]).encode()
    ).hexdigest()[:16]


@pytest.mark.parametrize("name", ["docbatch", "longgen"])
def test_stagger_start_cuts_each_callers_first_reply_only(name):
    tr = load(name)
    assert tr["stagger_start"] is True and tr["stagger_why"]
    n = tr["clients"]
    pool = trafficgen.requests(tr, tr["requests"], 50000, 2 ** 31 + 11)
    order = trafficgen.closed_order(tr, pool, n)
    sent = [next(order) for _ in range(n + 2 * len(pool))]
    for i, (req, whole) in enumerate(zip(sent[:n], pool)):
        assert req["staggered"] is True
        assert req["max_new"] == -(-whole["max_new"] * (i + 1) // n) >= 1
        assert req["prompt"] is whole["prompt"]
    assert sent[n - 1]["max_new"] == pool[n - 1]["max_new"]
    # from the n + 1-th on the pool as drawn, cycled; the pool itself
    # is never written to
    whole = (pool * 3)[n:n + 2 * len(pool)]
    assert all(a is b for a, b in zip(sent[n:], whole))
    assert not any("staggered" in r for r in pool)
    # the callers' first replies end at even distances over a cycle
    cut = [r["max_new"] for r in sent[:n]]
    mean = sum(r["max_new"] for r in pool) / len(pool)
    assert max(cut) - min(cut) > 0.7 * mean


@pytest.mark.parametrize("name,vocab,pinned", [
    ("turns", 65536, {7: "508428868d5d1bed",
                      2 ** 31 + 5: "2c68ed942a07ee29"}),
    ("threads", 100352, {7: "e5a5e7ae56b6a8a9",
                         2 ** 31 + 5: "47c674ae96340f45"})])
def test_without_the_key_the_pool_is_what_it_was(name, vocab, pinned):
    """The control cells' traffic: no ``stagger_start``, so what the
    callers send is the pool in order, seed for seed what the parent of
    PR 38 drew (the hashes are of its lengths)."""
    tr = load(name)
    assert "stagger_start" not in tr
    for seed, digest in pinned.items():
        pool = trafficgen.requests(tr, tr["requests"], vocab, seed)
        assert lengths_hash(pool) == digest
        order = trafficgen.closed_order(tr, pool, tr["clients"])
        assert all(next(order) is r for r in pool + pool)


def test_lead_in_requests_fall_outside_the_windows_count():
    """A closed loop's callers start ``lead_in_s`` before the window:
    their tokens from before it opens count nowhere, and a request sent
    before it has no time to its first token."""
    from benchmark import serving

    tr = load("docbatch")
    assert tr["lead_in_s"] == 2.0 and tr["lead_in_why"]
    t0, seconds = 100.0, 10.0
    done = {"finish": "length"}

    def record(sent, times):
        rec = serving.Record({"max_new": len(times)}, sent)
        rec.sent, rec.times, rec.doc = sent, times, done
        return rec

    early = record(t0 - 2.0, [t0 - 1.5, t0 - 1.0])          # all before
    across = record(t0 - 1.0, [t0 - 0.5, t0 + 0.5, t0 + 1.0])
    inside = record(t0 + 2.0, [t0 + 2.25, t0 + 2.5, t0 + 11.0])
    series, counters = serving.reduce_records(
        [early, across, inside], t0, seconds)
    assert counters["window_tokens_per_s"] == pytest.approx(4 / seconds)
    assert series["ttft_ms"] == pytest.approx([250.0])
    assert len(series["queue_wait_ms"]) == len(series["prefill_ms"]) == 1
    assert sorted(series["gap_ms"]) == pytest.approx([250.0, 500.0])
