"""The generator and the ``closed`` traffic kind: the same work for every
seed, token ids from the seed, and the closed-batch sizing rule on both
configurations."""
import math

import numpy as np
import pytest

import cell
import generator

ARCH = cell.arch("dense_swiglu")
TRAFFIC = cell.traffic_file("decode_long")
CLOSED = cell.traffic_kind("closed")
RUN_S = cell.benchmark()["run_seconds"]


def _plan(config, seed=0):
    cf = cell.config_file(config)
    m = ARCH.dims(cf)
    return (CLOSED.requests(TRAFFIC, ARCH, m, cf["engine"], RUN_S, seed), m,
            cf)


@pytest.mark.parametrize("config,contexts", [
    # qmsum quantiles 1/6, 1/2, 5/6
    ("internlm2-1.8b", [7985, 13966, 19947]),
    # the median: two sessions' contexts alone fill the 1792-page pool
    ("qwen1.5-7b-8L", [13966]),
])
def test_closed_batch_sizes(config, contexts):
    reqs, m, cf = _plan(config)
    assert TRAFFIC["kind"] == "closed"
    assert [len(r.prompt) for r in reqs] == contexts
    assert all(r.due_s is None for r in reqs)
    step = ARCH.step_weight_bytes(m) + sum(contexts) * \
        ARCH.kv_bytes_per_token(m)
    budget = math.ceil((RUN_S + TRAFFIC["budget"]["margin_s"]) * 819e9
                       / step)
    assert all(r.max_new_tokens == budget for r in reqs)
    e = cf["engine"]
    pages = sum(-(-(c + budget + e["decode_horizon"] + 1) // e["page_size"])
                for c in contexts)
    assert pages <= e["n_pages"]
    # one session more would not fit
    more = generator.quantile_lengths(TRAFFIC["context_tokens"],
                                      len(contexts) + 1)
    b2 = CLOSED.session_budget(TRAFFIC, ARCH, m, more, RUN_S)
    assert sum(-(-(c + b2 + e["decode_horizon"] + 1) // e["page_size"])
               for c in more) > e["n_pages"]


def test_seed_sets_ids_only():
    a, m, _ = _plan("internlm2-1.8b", seed=2 ** 31 + 7)
    b, _, _ = _plan("internlm2-1.8b", seed=2 ** 31 + 7)
    c, _, _ = _plan("internlm2-1.8b", seed=3)
    for x, y, z in zip(a, b, c):
        assert np.array_equal(x.prompt, y.prompt)
        assert len(x.prompt) == len(z.prompt)
        assert x.max_new_tokens == z.max_new_tokens
        assert not np.array_equal(x.prompt, z.prompt)
        assert x.prompt.dtype == np.int32
        assert 0 <= x.prompt.min() and x.prompt.max() < m["V"]


def test_quantiles_clip():
    d = {"mean": 10, "std": 100, "min": 5, "max": 20}
    assert generator.quantile_lengths(d, 4) == [5, 5, 20, 20]
