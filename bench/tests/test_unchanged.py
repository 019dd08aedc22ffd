"""The dense block behind its architecture module reads what it read
before it moved there: ``golden.json`` holds the weights' hashes, the
reference's gaps, the work counts, the readers' values on one record and
the closed batches' plans, recorded by the code as it stood then. Every
comparison is exact."""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import cell
import reference
import tiny

GOLD = json.loads((Path(__file__).parent / "golden.json").read_text())
ARCH = cell.arch("dense_swiglu")
CONFIGS = ["internlm2-1.8b", "qwen1.5-7b-8L"]


def _tiny_dims(dtype):
    cf = tiny.config(dtype)
    return ARCH.dims(cf, cell.model_config(cf).vocab_multiple)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 99])
def test_tiny_weights_bit_identical(dtype, seed):
    m = _tiny_dims(dtype)
    assert m == GOLD["tiny_dims"]
    got = {}
    for path, a in jax.tree_util.tree_flatten_with_path(
            ARCH.make(m, seed, dtype))[0]:
        a = np.asarray(a)
        got["/".join(p.key for p in path)] = [
            list(a.shape), str(a.dtype),
            hashlib.sha256(a.tobytes()).hexdigest()]
    assert got == GOLD["tiny_weights"][f"{dtype}/{seed}"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("control", [False, True])
def test_served_gaps_identical(dtype, control):
    m = _tiny_dims(dtype)
    params = ARCH.make(m, 3, dtype)
    rng = np.random.default_rng(1234)
    prompt = rng.integers(0, m["V"], 57).astype(np.int32)
    served = rng.integers(0, m["V"], 13).astype(np.int32)
    gaps = reference.served_gaps(ARCH, params, m, prompt, served,
                                 control=control)
    assert [float(x) for x in gaps] == GOLD["tiny_gaps"][f"{dtype}/{control}"]


@pytest.mark.parametrize("config", CONFIGS)
def test_work_identical(config):
    cf = cell.config_file(config)
    w, m, e = GOLD["work"][config], ARCH.dims(cf), cf["engine"]
    assert m == w["dims"]
    for c, v in w["decode_flops"].items():
        assert ARCH.decode_flops(m, int(c)) == v
    for n, v in w["prefill_flops"].items():
        assert ARCH.prefill_flops(m, int(n)) == v
    for c, v in w["kernel_work"].items():
        got = ARCH.kernel_work(m, int(c))
        assert {k: list(x) for k, x in got.items()} == v
    for cs, v in w["step_bytes"]:
        assert ARCH.step_bytes(m, cs) == v
    for t, v in w["session_pages"].items():
        assert ARCH.session_pages(m, e, int(t)) == v


@pytest.mark.parametrize("config", CONFIGS)
def test_readers_identical_on_one_record(config):
    g = GOLD["readers"][config]
    rec = dict(g["rec"], arch="dense_swiglu")
    for name in ("step_mfu", "paged_attn_roofline"):
        assert cell.metric_reader(name)(rec) == g[name], name


@pytest.mark.parametrize("config", CONFIGS)
def test_closed_plan_identical(config):
    cf = cell.config_file(config)
    e = cf["engine"]
    ctxs, budget = cell.traffic_kind("closed").plan(
        cell.traffic_file("decode_long"), ARCH, ARCH.dims(cf), e,
        cell.benchmark()["run_seconds"])
    pages = sum(ARCH.session_pages(ARCH.dims(cf), e,
                                   c + budget + e["decode_horizon"] + 1)
                for c in ctxs)
    assert {"contexts": ctxs, "budget": budget, "pages": pages} == \
        GOLD["plan"][config]
