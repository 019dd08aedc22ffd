"""The harness end to end on the CPU at a tiny size, through its
test-only entry (no look for a chip): a sound run is correct, and a run
with the timed path broken underneath (``bench/faults.py``) is not."""
import numpy as np
import pytest

import cell
import faults
import reference
import tiny


def test_sound_run_is_correct():
    res, rec = tiny.run(tiny.make())
    assert res["correct"], res
    assert res["failed"] == 0 and res["attempted"] == len(rec["requests"])
    assert set(res["metrics"]) == {"out_tok_s", "setup_s"}
    assert res["metrics"]["out_tok_s"]["value"] > 0
    assert list(res)[-1] == "check"
    assert res["check"]["max_logit_gap"]["value"] <= 1e-3
    # every session was served tokens in the window, stamped in order
    for q in rec["requests"]:
        assert q["out_end"] > q["out_start"] >= 1
        assert 0 <= q["first_s"] <= q["last_s"] <= rec["window_s"]
    assert len(rec["pages_in_use"]) == rec["ticks"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_fails(fault):
    undo = faults.install(fault)
    try:
        res, _ = tiny.run(tiny.make())
    finally:
        undo()
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > 1e-3


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_control_fails_where_the_program_passes(seed):
    """The float8 control, read at the positions of the served tokens,
    lies well past the bf16 program's widest gap at the same size."""
    res, rec = tiny.run(tiny.make(limit=1e9, dtype="bfloat16"), seed=seed)
    program = res["check"]["max_logit_gap"]["value"]
    arch = cell.arch(rec["arch"])
    m = arch.dims(tiny.config("bfloat16"), 16)
    params = arch.make(m, seed, "bfloat16")
    worst = max(float(reference.served_gaps(
        arch, params, m, rec["prompts"][rid], np.asarray(served),
        control=True).max()) for rid, served in rec["served"].items())
    assert worst >= 3 * program, (worst, program)
