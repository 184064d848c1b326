"""The one traffic generator: a traffic file of parameters in, requests out.

A traffic file (``bench/traffic/<name>.json``) gives the slots, the tier
and the length distributions of a saturated mix::

    {"slots": 32, "tier_bits": 8,
     "prompt": {"median": 128, "min": 32, "max": 512},
     "output": {"median": 384, "min": 128, "max": 1024}, ...}

Lengths are lognormal about ``median``, cut to ``[min, max]``, with
``sigma = ln(max / median) / 2`` (so ``max`` sits two sigma out), and
never rounded; ``min == max`` gives one fixed length. The work does not
depend on the seed: each block of :data:`BLOCK` requests holds the same
(prompt, output) pairs, taken at the block's evenly spaced quantiles and
paired by a fixed permutation. The seed only orders each block and draws
the prompt tokens. So every seed, and every prefix of whole blocks, asks
for the same work. All :data:`N_REQUESTS` requests are greedy and queued
at once (saturated: more requests wait than there are slots, and more
than any window can serve).
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

#: fixed pairing of prompt and output quantiles (independent of --seed)
PAIRING_SEED = 20260417
#: requests a block holds, each at its own quantile
BLOCK = 32
#: requests queued for one run
N_REQUESTS = 1024


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the evenly spaced quantiles of the cut lognormal."""
    median, lo, hi = dist["median"], dist["min"], dist["max"]
    sigma = math.log(hi / median) / 2
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return np.clip(np.rint(median * np.exp(sigma * np.asarray(z))), lo, hi).astype(np.int64)


def block_pairs(spec: dict) -> np.ndarray:
    """(BLOCK, 2) array of (prompt_len, max_new_tokens), seed-independent."""
    prompts = quantile_lengths(spec["prompt"], BLOCK)
    outputs = quantile_lengths(spec["output"], BLOCK)
    perm = np.random.default_rng(PAIRING_SEED).permutation(BLOCK)
    return np.stack([prompts, outputs[perm]], axis=1)


def requests(spec: dict, vocab_size: int, seed: int, n: int = N_REQUESTS) -> list[dict]:
    """``n`` requests: ``{"rid", "tokens", "max_new_tokens"}``."""
    rng = np.random.default_rng(int(seed))
    pairs = block_pairs(spec)
    out = []
    while len(out) < n:
        for s, new in pairs[rng.permutation(len(pairs))]:
            if len(out) == n:
                break
            toks = rng.integers(0, vocab_size, int(s), dtype=np.int32)
            out.append({"rid": len(out), "tokens": toks, "max_new_tokens": int(new)})
    return out


def max_extent(spec: dict) -> int:
    """Most positions one request can hold: its longest prompt and output."""
    return int(spec["prompt"]["max"] + spec["output"]["max"])


def chunk_lengths(spec: dict, chunk: int) -> list[int]:
    """Every chunk length the mix's prompts can produce under chunked
    prefill of ``chunk`` tokens: full chunks plus each remainder."""
    p = spec["prompt"]
    lens = set()
    for s in range(p["min"], p["max"] + 1):
        lens.add(min(s, chunk))
        if s % chunk:
            lens.add(s % chunk)
    return sorted(lens)
