"""The traffic generator's shared pieces: a request, token ids from the
seed, and lengths from a mix's distribution.

A traffic mix is a data file, ``bench/traffic/<mix>.json``, that names its
kind (``"kind"``). The kind is a module of its own,
``bench/traffic/<kind>.py``, found by that name (``cell.traffic_kind``):
it makes the mix's requests from the seed (``requests(mix, arch, m,
engine, run_seconds, seed)``, with the configuration's architecture module
``arch`` and its dims ``m``), sets them up (``setup``) and drives them
through the timed window (``drive``), through
the harness's ``Run``, which stamps what happens on the host clock. So a
new mix of a kind that exists is one data file, and a new kind is one
module, with no edit to the harness.

The seed chooses token ids only: the sizes, budgets and arrival times of
a mix follow from its parameters and the configuration, so every seed
gives the same work.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Request:
    req_id: int
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int
    due_s: float | None = None  # seconds after the window opens; None: set-up


def token_rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (2 ** 63), 1]))


def token_ids(rng: np.random.Generator, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int32)


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """Lengths at the quantiles (i + 0.5) / n of the clipped normal
    ``dist`` (``mean``, ``std``, ``min``, ``max``)."""
    nd = NormalDist(dist["mean"], dist["std"])
    return [int(min(dist["max"], max(dist["min"],
                                     round(nd.inv_cdf((i + 0.5) / n)))))
            for i in range(n)]
