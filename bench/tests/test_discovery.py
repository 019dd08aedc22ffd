"""A new cell, configuration, traffic mix, traffic kind and per-layer
metric are each one file found by name: the harness runs them with no code
edit. And the command refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import cell
import harness
import tiny

READER = '''"""Ticks in the window."""


def read(rec):
    return float(rec["ticks"])
'''

# a second traffic kind: the mix's sessions all fall due as the window
# opens, so their prefill runs inside it
KIND = '''"""Traffic kind ``at_open``."""
from dataclasses import replace

import generator


def requests(mix, arch, m, engine, run_seconds, seed):
    rng = generator.token_rng(seed)
    return [generator.Request(i, generator.token_ids(rng, m["V"], n),
                              mix["new_tokens"], due_s=0.0)
            for i, n in enumerate(mix["prompts"])]


def setup(run, reqs):
    run.warm_decode(max(len(r.prompt) + r.max_new_tokens for r in reqs))


def drive(run, reqs, seconds):
    for r in reqs:
        run.submit(r)
    while run.elapsed() < seconds:
        run.tick()
    run.quiesce()
'''

TTFT = '''"""Client: the latest first token, from its due time."""


def read(rec):
    t = [q["first_s"] - q["due_s"] for q in rec["requests"]
         if q["first_s"] is not None]
    return max(t) if t else None
'''


def test_new_files_found_by_name(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(cell.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = cell.benchmark()
    bm["configs"].append({"name": "tiny2", "file": "bench/configs/tiny2.json"})
    bm["workloads"].append({"name": "tiny2.small", "config": "tiny2",
                            "traffic": "small", "chips": 1})
    bm["per_layer"].append({"name": "ticks_in_window", "unit": "ticks",
                            "workloads": ["tiny2.small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    (bench / "configs" / "tiny2.json").write_text(json.dumps(tiny.config()))
    (bench / "traffic" / "small.json").write_text(json.dumps(tiny.traffic()))
    (bench / "limits" / "tiny2.small.json").write_text(json.dumps(
        {"max_logit_gap": {"limit": 1e-3}}))
    (bench / "metrics" / "ticks_in_window.py").write_text(READER)
    monkeypatch.setattr(cell, "BENCH", bench)
    monkeypatch.setattr(cell, "REPO", tmp_path)

    c = harness.Cell.by_name("tiny2.small")
    assert c.config["name"] == "tiny" and c.traffic["kind"] == "closed"
    assert [m["name"] for m in c.per_layer] == ["ticks_in_window"]
    assert {m["name"] for m in c.end_to_end} == {"out_tok_s", "setup_s"}
    c.run_seconds = 1.0
    res, rec = tiny.run(c)
    assert res["correct"] and res["metrics"]["out_tok_s"]["value"] > 0
    assert cell.metric_reader("ticks_in_window")(rec) == rec["ticks"]


def test_new_traffic_kind_found_by_name(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(cell.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bm = cell.benchmark()
    bm["configs"].append({"name": "tiny2", "file": "bench/configs/tiny2.json"})
    bm["workloads"].append({"name": "tiny2.burst", "config": "tiny2",
                            "traffic": "burst", "chips": 1})
    bm["end_to_end"].append({"name": "ttft_max_s", "unit": "s",
                             "workloads": ["tiny2.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    (bench / "configs" / "tiny2.json").write_text(json.dumps(tiny.config()))
    (bench / "traffic" / "at_open.py").write_text(KIND)
    (bench / "traffic" / "burst.json").write_text(json.dumps(
        {"kind": "at_open", "prompts": [40, 90, 130], "new_tokens": 12,
         "check_requests": 3}))
    (bench / "limits" / "tiny2.burst.json").write_text(json.dumps(
        {"max_logit_gap": {"limit": 1e-3}}))
    (bench / "metrics" / "ttft_max_s.py").write_text(TTFT)
    monkeypatch.setattr(cell, "BENCH", bench)
    monkeypatch.setattr(cell, "REPO", tmp_path)

    c = harness.Cell.by_name("tiny2.burst")
    c.run_seconds = 1.0
    res, rec = tiny.run(c, seconds=3.0)
    assert res["correct"] and res["failed"] == 0, res
    assert res["attempted"] == 3
    assert {"out_tok_s", "setup_s", "ttft_max_s"} == set(res["metrics"])
    for q in rec["requests"]:
        # served in the window: the prefill's token and twelve more
        assert q["out_start"] == 0 and q["out_end"] == 13
        assert q["due_s"] == 0.0 <= q["submit_s"] <= q["first_s"]
    assert res["metrics"]["ttft_max_s"]["value"] == max(
        q["first_s"] for q in rec["requests"])


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cell.BENCH / "run.py"), "--workload",
         "internlm2-1.8b.decode_long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
