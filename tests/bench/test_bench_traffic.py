"""The traffic generator repeats by seed and gives every seed the same
sizes."""
import collections
import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import manifest as mf, traffic  # noqa: E402

BIG = 2 ** 31 + 12345


def _mix(name):
    return mf.load_json(ROOT / "bench" / "traffic" / f"{name}.json")


def test_filterbank_repeats_by_seed():
    mix = dict(_mix("block16k"), pool_flushes=2, requests_per_flush=3,
               samples=1024)
    a = traffic.filterbank_pool(mix, BIG)
    b = traffic.filterbank_pool(mix, BIG)
    c = traffic.filterbank_pool(mix, BIG + 1)
    for fa, fb in zip(a, b):
        for x, y in zip(fa, fb):
            np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0][0], c[0][0])
    assert all(len(x) == mix["samples"] for f in a for x in f)


def test_testbed_signal_has_its_bands():
    g = traffic.rng(BIG, "t")
    mix = _mix("block16k")
    x = traffic.testbed_signal(g, mix["samples"], mix["bands"],
                               mix["noise_psd_db"])
    f = np.fft.rfftfreq(len(x))
    p = np.abs(np.fft.rfft(x)) ** 2
    inside = np.zeros_like(f, bool)
    for lo, hi in mix["bands"]:
        inside |= (f >= lo) & (f <= hi)
    assert p[inside].sum() / p.sum() > 0.99
    assert abs(np.var(x) - 3.0) < 0.3       # three unit-power bands


def test_lm_requests_repeat_by_seed_and_share_sizes():
    mix = _mix("chat")
    block = mix["block"]
    take = lambda s: list(itertools.islice(
        traffic.lm_requests(mix, s, 151936), 4 * block))
    a, b, c = take(BIG), take(BIG), take(7)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    assert [r["max_new"] for r in a] != [r["max_new"] for r in c]
    buckets = mix["prompt_len"]["buckets"]
    out = mix["output_len"]
    for reqs in (a, c):
        assert all(len(r["prompt"]) in buckets for r in reqs)
        assert all(out["min"] <= r["max_new"] <= out["max"] for r in reqs)
        assert all(0 <= t < 151936 for r in reqs for t in r["prompt"])
    # every block of requests asks for the same lengths, in another order
    sizes = lambda reqs, key: [sorted(key(r) for r in reqs[i:i + block])
                               for i in range(0, len(reqs), block)]
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sizes(a, key) == sizes(c, key)
        assert len(set(map(str, sizes(a, key)))) == 1


def test_lm_lengths_follow_the_stated_distribution():
    mix = _mix("chat")
    for spec in (mix["prompt_len"], mix["output_len"]):
        got = traffic.lognormal_quantiles(spec, 4000)
        mean = spec["median"] * np.exp(spec["sigma"] ** 2 / 2)
        assert np.median(got) == pytest.approx(spec["median"], rel=0.01)
        assert np.mean(got) == pytest.approx(mean, rel=0.02)
    # with the file's buckets, each prompt rounds up to the next bucket
    p, _ = traffic.lm_block(_mix("chat"))
    raw = traffic.lognormal_quantiles(_mix("chat")["prompt_len"], len(p))
    b = _mix("chat")["prompt_len"]["buckets"]
    assert all(x == min([k for k in b if k >= r] or [b[-1]])
               for x, r in zip(np.sort(p), np.sort(raw)))


def test_reservoir_is_seeded_and_uniform():
    from bench.check import Reservoir

    def sample(seed, batches=50, n=64, k=16):
        r = Reservoir(k, traffic.rng(seed, "check"))
        for b in range(batches):
            for j, slot in r.offer(n):
                r.put(slot, b * n + j)
        return r.items
    a = sample(BIG)
    assert a == sample(BIG) and a != sample(BIG + 1)
    assert len(a) == 16 and len(set(a)) == 16
    hits = np.zeros(3200)
    for s in range(300):
        hits[sample(s)] += 1
    # each of 3,200 items is kept with probability 16/3200: 1.5 per 300
    assert hits[:1600].sum() == pytest.approx(hits[1600:].sum(), rel=0.25)
