"""The traffic generator: deterministic per seed, different across seeds,
and the same work (lengths, arrival times) for every seed."""
import itertools

import pytest

from bench.harness import spec
from bench.harness.traffic import (STRATA, Traffic, arrival_times,
                                   mean_rate, output_lengths)

BIG = 2 ** 31 + 12345      # seeds wider than 32 signed bits


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_mix_files_generate(mix):
    m = spec.load_json(f"{spec.BENCH_DIR}/traffic/{mix}.json")
    t = Traffic(m, 1000, BIG)
    reqs = t.open_loop(5.0) if not t.closed else \
        list(itertools.islice(t.closed_loop(), 50))
    assert reqs and all(len(r.prompt) == m["prompt_len"] for r in reqs)
    assert all(3 <= x < 1000 for r in reqs for x in r.prompt)
    out = m["output"]
    assert all(1 <= r.max_new <= out["max"] for r in reqs)


def _chat():
    return spec.load_json(f"{spec.BENCH_DIR}/traffic/chat.json")


def test_open_loop_is_deterministic_per_seed_and_differs_across_seeds():
    a = Traffic(_chat(), 151936, BIG).open_loop(20.0)
    b = Traffic(_chat(), 151936, BIG).open_loop(20.0)
    c = Traffic(_chat(), 151936, BIG + 1).open_loop(20.0)
    assert a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]
    assert [r.max_new for r in a] != [r.max_new for r in c]
    # the same work in another order: arrival times and length multiset
    assert [r.t for r in a] == [r.t for r in c]
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)


def test_closed_loop_is_deterministic_and_cycles_one_length_set():
    m = spec.load_json(f"{spec.BENCH_DIR}/traffic/batch.json")
    n, cl = m["clients"] * 4, m["clients"]
    take = lambda s: list(itertools.islice(
        Traffic(m, 151936, s).closed_loop(), cl + n * 2))
    a, b, c = take(7), take(7), take(BIG)
    assert a == b and a != c
    cycle = sorted(output_lengths(m["output"], n))
    # after the clients' first requests, every cycle holds the same lengths
    for reqs in (a, c):
        assert sorted(r.max_new for r in reqs[cl:cl + n]) == cycle
        assert sorted(r.max_new for r in reqs[cl + n:]) == cycle


def test_closed_loop_first_requests_are_the_same_work_for_every_seed():
    m = spec.load_json(f"{spec.BENCH_DIR}/traffic/batch.json")
    cl = m["clients"]
    firsts = [sorted(r.max_new for r in itertools.islice(
        Traffic(m, 151936, s).closed_loop(), cl)) for s in (1, 2, BIG)]
    assert firsts[0] == firsts[1] == firsts[2]
    # shares of a quantile, evenly spaced: a spread of remaining lengths
    assert len(set(firsts[0])) > cl // 2
    assert max(firsts[0]) < m["output"]["max"]


def test_each_run_of_requests_takes_one_length_per_stratum():
    reqs = Traffic(_chat(), 151936, BIG).open_loop(40.0)
    lens = sorted(r.max_new for r in reqs)
    k = -(-len(lens) // STRATA)
    stratum = {}
    for j in range(STRATA):
        for m in lens[j * k:(j + 1) * k]:
            stratum.setdefault(m, set()).add(j)
    full_runs = len(lens) - (STRATA - 1) * k      # the last stratum's size
    assert full_runs > 20
    for b in range(0, full_runs * STRATA, STRATA):
        run = [r.max_new for r in reqs[b:b + STRATA]]
        # a length may sit on a stratum border; the run still spans all
        assert len(set.union(*(stratum[m] for m in run))) == STRATA


def test_output_lengths_are_clipped_lognormal_quantiles():
    out = {"median": 128, "sigma": 0.8, "min": 16, "max": 512}
    lens = output_lengths(out, 1000)
    assert lens == sorted(lens)
    assert min(lens) == 16 and max(lens) == 512
    assert lens[500] == 128 or lens[499] == 128


def test_arrivals_follow_the_burst_rate():
    mix = {"rate_rps": 50.0, "burst": {"every_s": 10, "len_s": 2, "x": 3},
           "arrival_seed": 3}
    ts = arrival_times(mix, 200.0)
    inside = sum(1 for t in ts if t % 10 < 2)
    # 2 s at 150/s against 8 s at 50/s in every 10 s
    assert inside / (len(ts) - inside) == pytest.approx(300 / 400, rel=0.1)
    assert len(ts) / 200.0 == pytest.approx(mean_rate(mix), rel=0.05)
    assert mean_rate(mix) == pytest.approx(70.0)
