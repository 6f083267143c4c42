"""The one traffic generator: arrivals, popularity and request texts.

A traffic mix is a JSON file of parameters under ``bench/traffic/``; this
module turns it and a seed into concrete requests.  Its arithmetic is
copied from the program so that a later change to the program cannot move
the yardstick:

* `poisson_arrivals` is ``traffic/arrivals.py``'s homogeneous Poisson
  process (exponential gaps, cumulated in chunks until the horizon), drawn
  from numpy's generator instead of ``jax.random`` so that it needs no
  device;
* `ycsb_zipfian` is YCSB's ZipfianGenerator (Gray et al., "Quickly
  generating billion-record synthetic databases", SIGMOD 1994), the
  popularity law of the YCSB core workloads.

Every stream is drawn from ``numpy.random.SeedSequence([seed, stream])``,
so the same seed gives the same requests, and seeds above 2**32 work.
"""
from __future__ import annotations

import numpy as np

# streams of one seed (fixed ids: a new stream gets a new id)
STREAM_ARRIVALS = 1
STREAM_TEXTS = 2
STREAM_CATALOG = 3
STREAM_PROGRAM = 4
STREAM_FAILED = 6


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program's own generators (``jax.random``
    keys hold 32 signed bits), derived from the benchmark seed."""
    state = np.random.SeedSequence([int(seed), STREAM_PROGRAM]).generate_state(1)
    return int(state[0] & 0x7FFFFFFF)


def poisson_arrivals(rng: np.random.Generator, rate: float,
                     horizon_s: float) -> np.ndarray:
    """Homogeneous Poisson arrival times (s) on [0, horizon) at ``rate``."""
    if rate <= 0.0 or horizon_s <= 0.0:
        return np.zeros((0,), np.float64)
    times, t0 = [], 0.0
    mean_n = rate * horizon_s
    chunk = int(mean_n + 6.0 * np.sqrt(mean_n) + 16.0)
    while t0 < horizon_s:
        gaps = rng.exponential(1.0, chunk) / rate
        t = t0 + np.cumsum(gaps)
        times.append(t)
        t0 = float(t[-1])
    out = np.concatenate(times)
    return out[out < horizon_s]


def ycsb_zipfian(rng: np.random.Generator, n_items: int, theta: float,
                 size: int) -> np.ndarray:
    """``size`` ranks in [0, n_items) from YCSB's zipfian distribution
    with constant ``theta`` (rank 0 is the most popular)."""
    ranks = np.arange(1, n_items + 1, dtype=np.float64)
    zetan = float(np.sum(ranks ** -theta))
    u = rng.random(size)
    if n_items <= 2:
        return (u * zetan >= 1.0).astype(np.int64)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n_items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    out = (n_items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    out = np.where(uz < 1.0 + 0.5 ** theta, 1, out)
    out = np.where(uz < 1.0, 0, out)
    return np.clip(out, 0, n_items - 1)


def catalog_texts(catalog: dict, spec: dict, rng: np.random.Generator,
                  n: int) -> list:
    """``n`` tool calls against a generated catalog.

    The target tool of each call is drawn YCSB-zipfian over a seeded
    permutation of the catalog's tools; its text is ``words_min`` to
    ``words_max`` words, each drawn from the target's description or, with
    probability ``general_share``, from the catalog's general words."""
    tools = catalog["tool_words"]                 # list of word lists
    general = catalog["general_words"]
    perm = rng.permutation(len(tools))
    targets = perm[ycsb_zipfian(rng, len(tools), float(spec["zipf_theta"]), n)]
    lengths = rng.integers(int(spec["words_min"]), int(spec["words_max"]) + 1, n)
    share = float(spec["general_share"])
    out = []
    for t, k in zip(targets, lengths):
        words = tools[t]
        pick = rng.integers(0, len(words), k)
        gen = rng.random(k) < share
        gpick = rng.integers(0, len(general), k)
        out.append(" ".join(
            general[g] if is_g else words[w]
            for w, g, is_g in zip(pick, gpick, gen)
        ))
    return out


def texts(catalog: dict, spec: dict, seed: int, n: int) -> list:
    """``n`` request texts of the traffic mix's ``text`` section."""
    rng = rng_for(seed, STREAM_TEXTS)
    source = spec["source"]
    if source == "catalog_zipfian":
        return catalog_texts(catalog, spec, rng, n)
    raise ValueError(f"unknown text source {source!r}")
