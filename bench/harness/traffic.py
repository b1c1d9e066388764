"""Seeded draws shared by every arrival process.

A traffic mix is a data file of parameters, ``bench/traffic/<mix>.json``.
Its ``arrivals`` key names the arrival process, a module
``bench/arrivals/<arrivals>.py`` found by name (``harness.spec.arrivals``):

  * a closed loop sets ``LOOP = "closed"``: one client sends each
    request when the last one has finished, for the length of the window;
  * an open loop sets ``LOOP = "open"`` and has
    ``schedule(params, config, seconds, seed) -> (due offsets, sizes)``.

Every draw comes from ``--seed`` through numpy's SeedSequence, so a
seed of any size gives the same inputs each time.
"""
from __future__ import annotations

from typing import List, Sequence

import numpy as np

SEED_MASK = 0x7FFFFFFF


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def derived_seeds(seed: int, count: int) -> List[int]:
    """``count`` 31-bit seeds for the program, drawn from ``seed``."""
    ss = np.random.SeedSequence([int(seed), 7, 0])
    return [int(s) & SEED_MASK for s in ss.generate_state(count)]


def exact_counts(weights: Sequence[float], total: int) -> List[int]:
    """``total`` split in proportion to ``weights`` (largest remainder)."""
    w = np.asarray(weights, np.float64)
    share = w / w.sum() * total
    counts = np.floor(share).astype(int)
    rest = total - counts.sum()
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:rest]] += 1
    return counts.tolist()


def request_sizes(config: dict, total: int, r: np.random.Generator
                  ) -> List[int]:
    """``total`` request sizes: the configuration's ``vertex_counts`` in
    exact proportion to its ``vertex_count_weights``, shuffled."""
    sizes = config["vertex_counts"]
    counts = exact_counts(config.get("vertex_count_weights",
                                     [1] * len(sizes)), total)
    pool = np.repeat(np.asarray(sizes), counts)
    r.shuffle(pool)
    return pool.tolist()
