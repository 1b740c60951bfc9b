"""The one traffic generator.  A mix is a parameter file in ``traffic/``.

Every draw comes from the run's ``--seed``.  Sizes do not depend on the
seed: each seed gets the same set of lengths in another order, so two
seeds ask for the same work.

``filterbank`` mixes: blocks of real samples, the paper's SNR testbed
signal (arXiv:2003.06727 Fig. 7, after Shim & Shanbhag): three unit-power
Gaussian noises ideally band-limited to the pass, transition and stop
bands, plus white noise at ``noise_psd_db``.

``lm`` mixes: prompts of uniformly drawn token ids.  Prompt and output
lengths follow the log-normal distributions the mix states, drawn by
strata: every block of ``block`` requests holds the lengths at the
quantiles (i + 1/2) / block, the prompt lengths rounded up to the mix's
buckets (each bucket is one prefill program), the output lengths clipped
to the mix's range; the seed shuffles which prompt goes with which output
and the order within each block.
"""
from __future__ import annotations

import zlib
from typing import Dict, Iterator, List

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream); any integer seed."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64),
                                zlib.crc32(stream.encode())]))


def _bandlimited(g: np.random.Generator, n: int, lo: float,
                 hi: float) -> np.ndarray:
    spec = np.fft.rfft(g.standard_normal(n))
    f = np.fft.rfftfreq(n)
    spec[~((f >= lo) & (f <= hi))] = 0.0
    sig = np.fft.irfft(spec, n)
    return sig / sig.std()


def testbed_signal(g: np.random.Generator, n: int, bands,
                   noise_psd_db: float) -> np.ndarray:
    x = sum(_bandlimited(g, n, lo, hi) for lo, hi in bands)
    return x + g.standard_normal(n) * np.sqrt(10.0 ** (noise_psd_db / 10.0))


def filterbank_pool(mix: Dict, seed: int) -> List[List[np.ndarray]]:
    """``pool_flushes`` flushes of ``requests_per_flush`` signals each; the
    client cycles through them."""
    g = rng(seed, "filterbank")
    return [[testbed_signal(g, mix["samples"], mix["bands"],
                            mix["noise_psd_db"])
             for _ in range(mix["requests_per_flush"])]
            for _ in range(mix["pool_flushes"])]


def lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` strata midpoints of a log-normal with the stated median
    and sigma."""
    from scipy.stats import norm
    p = (np.arange(n) + 0.5) / n
    return spec["median"] * np.exp(spec["sigma"] * norm.ppf(p))


def lm_block(mix: Dict):
    """(prompt lengths, output lengths) of one block, in quantile order."""
    n = mix["block"]
    buckets = np.asarray(mix["prompt_len"]["buckets"])
    raw = lognormal_quantiles(mix["prompt_len"], n)
    idx = np.minimum(np.searchsorted(buckets, raw), len(buckets) - 1)
    out = mix["output_len"]
    new = np.clip(np.round(lognormal_quantiles(out, n)), out["min"],
                  out["max"]).astype(int)
    return buckets[idx].astype(int), new


def lm_lengths(mix: Dict, seed: int) -> Iterator[tuple]:
    """(prompt length, output length) pairs, block after block."""
    g = rng(seed, "lm_lengths")
    prompts, news = lm_block(mix)
    while True:
        pairs = list(zip(g.permutation(prompts).tolist(),
                         g.permutation(news).tolist()))
        yield from pairs


def lm_requests(mix: Dict, seed: int, vocab: int) -> Iterator[dict]:
    """Endless stream of {"prompt": [ids], "max_new": n}."""
    g = rng(seed, "lm_tokens")
    for n, new in lm_lengths(mix, seed):
        yield {"prompt": g.integers(0, vocab, n).tolist(),
               "max_new": int(new)}
