"""The traffic generator: deterministic per seed, inside its stated
ranges and ``max_len``, and the same set of sizes on every seed."""

import numpy as np
import pytest
from bench_helpers import mix_file

from bench import traffic

MAX_LEN, VOCAB = 2048, 49152


def _mix(name):
    return mix_file(name)


def _closed(seed, n=200):
    gen = traffic.Traffic(_mix("offline"), vocab=VOCAB, max_len=MAX_LEN,
                          seed=seed)
    return gen.first_wave() + [gen.next_closed() for _ in range(n)]


def _sig(reqs):
    return [(len(r.prompt), r.max_new, r.prompt[:4].tolist()) for r in reqs]


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 2**40 + 3])
def test_closed_loop_is_deterministic_per_seed(seed):
    assert _sig(_closed(seed)) == _sig(_closed(seed))
    assert _sig(_closed(seed)) != _sig(_closed(seed + 1))


def test_closed_loop_lengths_stay_in_range():
    mix = _mix("offline")
    reqs = _closed(7, n=512)
    wave, rest = reqs[:mix["concurrency"]], reqs[mix["concurrency"]:]
    for r in reqs:
        assert mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
        assert len(r.prompt) + r.max_new <= MAX_LEN
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < VOCAB
    for r in rest:
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
    # the first wave's residual lengths spread completions out
    assert 1 <= min(r.max_new for r in wave)
    assert max(r.max_new for r in wave) <= mix["output"]["max"]
    assert len({r.max_new for r in wave}) > mix["concurrency"] // 2


def test_every_seed_offers_the_same_sizes():
    block = _mix("offline")["block"]
    a, b = _closed(1, n=block), _closed(99, n=block)
    wave = _mix("offline")["concurrency"]
    sizes = lambda reqs: sorted(r.max_new for r in reqs[wave:wave + block])
    assert sizes(a) == sizes(b)
    assert [r.max_new for r in a[wave:]] != [r.max_new for r in b[wave:]]


def _chat_run(seed, horizon=60.0):
    """Drive the open loop as a server that answers each request 0.5 s
    after it is due with answer tokens derived from its id."""
    mix = _mix("chat")
    gen = traffic.Traffic(mix, vocab=VOCAB, max_len=MAX_LEN, seed=seed)
    out, t = [], 0.0
    while t < horizon:
        t = min(gen.next_due(), horizon)
        for r in gen.due(t):
            out.append(r)
            answer = [(r.rid * 7 + i) % VOCAB for i in range(r.max_new)]
            gen.on_finish(r, r.due + 0.5, answer)
    return mix, gen, out


def test_chat_sessions_follow_the_mix():
    mix, gen, reqs = _chat_run(3)
    sys_len = mix["system_prompt"]
    by_session = {}
    for r in reqs:
        assert len(r.prompt) + r.max_new <= MAX_LEN
        assert np.array_equal(r.prompt[:sys_len], gen.system)
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        by_session.setdefault(r.session, []).append(r)
    assert len(by_session) > 20
    for turns in by_session.values():
        assert len(turns) <= mix["turns"]["max"]
        assert [r.turn for r in turns] == list(range(1, len(turns) + 1))
        for prev, nxt in zip(turns, turns[1:]):
            answer = [(prev.rid * 7 + i) % VOCAB for i in range(prev.max_new)]
            head = np.concatenate([prev.prompt, answer])
            assert np.array_equal(nxt.prompt[:len(head)], head)
            msg = len(nxt.prompt) - len(head)
            assert mix["prompt"]["min"] <= msg <= mix["prompt"]["max"]
            assert nxt.due > prev.due + 0.5       # after finish + think time
    # Poisson session starts at the mix's rate
    starts = sorted(t[0].due for t in by_session.values())
    rate = (len(starts) - 1) / (starts[-1] - starts[0])
    assert 0.6 * mix["rate_sessions_s"] < rate < 1.6 * mix["rate_sessions_s"]


def test_chat_is_deterministic_per_seed():
    sig = lambda reqs: [(r.session, r.turn, round(r.due, 9), len(r.prompt),
                         r.max_new, r.prompt[-3:].tolist()) for r in reqs]
    assert sig(_chat_run(5)[2]) == sig(_chat_run(5)[2])
    assert sig(_chat_run(5)[2]) != sig(_chat_run(6)[2])


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16,
      "max": 384}, 16, 384),
    ({"dist": "exponential", "mean": 2.0}, 0.0, float("inf")),
    ({"dist": "uniform_int", "min": 1, "max": 4}, 1, 4),
])
def test_quantiles_are_monotone_and_clipped(dist, lo, hi):
    qs = [traffic.quantile(dist, (i + 0.5) / 64) for i in range(64)]
    assert qs == sorted(qs)
    assert lo <= qs[0] and qs[-1] <= hi
    if dist["dist"] == "uniform_int":
        assert sorted(set(qs)) == [1, 2, 3, 4]


@pytest.mark.parametrize("dist", [
    {"dist": "gamma", "mean": 0.5, "cv": 3.0},
    {"dist": "mixture", "parts": [
        {"weight": 3, "dist": "lognormal", "median": 64, "sigma": 0.5,
         "min": 16, "max": 256},
        {"weight": 1, "dist": "lognormal", "median": 1500, "sigma": 0.1,
         "min": 1024, "max": 1800}]},
])
def test_bursty_and_mixed_quantiles_are_monotone(dist):
    qs = [traffic.quantile(dist, (i + 0.5) / 256) for i in range(256)]
    assert qs == sorted(qs)
    if dist["dist"] == "gamma":
        mean = sum(qs) / len(qs)
        assert 0.35 < mean < 0.55          # the block holds the mean...
        assert qs[len(qs) // 2] < 0.5 * mean   # ...with most gaps short
    else:
        short = [q for q in qs if q <= 256]
        assert len(short) == 192 and all(1024 <= q <= 1800 for q in qs[192:])


def _open_run(mix, seed=4, horizon=40.0):
    gen = traffic.Traffic(mix, vocab=VOCAB, max_len=MAX_LEN, seed=seed)
    out, t = [], 0.0
    while t < horizon:
        t = min(gen.next_due(), horizon)
        for r in gen.due(t):
            out.append(r)
            gen.on_finish(r, r.due + 0.5, [1] * r.max_new)
    return gen, out


def test_unshared_chat_keeps_lengths_and_shares_nothing():
    """``shared`` false and ``history`` false: the same message and answer
    sizes as ``chat``, with no prompt sharing a prefix with another."""
    mix = dict(_mix("chat"), shared=False, history=False)
    _, reqs = _open_run(mix)
    sys_len = mix["system_prompt"]
    assert len(reqs) > 20
    for r in reqs:
        assert mix["prompt"]["min"] <= len(r.prompt) - sys_len <= (
            mix["prompt"]["max"])
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
    heads = {tuple(r.prompt[:8].tolist()) for r in reqs}
    assert len(heads) == len(reqs)


def test_bursty_arrivals_keep_the_rate():
    mix = dict(_mix("chat"), burst_cv=3.0, rate_sessions_s=4.0)
    _, reqs = _open_run(mix, horizon=200.0)
    starts = sorted(r.due for r in reqs if r.turn == 1)
    gaps = np.diff(starts)
    rate = (len(starts) - 1) / (starts[-1] - starts[0])
    assert 0.6 * 4.0 < rate < 1.6 * 4.0
    assert np.std(gaps) / np.mean(gaps) > 1.8      # burstier than Poisson
