"""The seam between the harness and the block: everything that depends on
the block comes from the configuration's architecture module
(``bench/arch/<name>.py``), and the shared files name no block."""
import ast
import re
import shutil
import time

import jax
import pytest

import cell
import harness
import scopes
import tiny

MS = 1_000_000
INTERFACE = ("check", "dims", "make", "hidden", "decode_flops",
             "prefill_flops", "kernel_work", "step_bytes", "session_pages")

# a test-only architecture: the dense block, each call recorded, with a
# kernel name of its own
SPY = f'''"""A test-only architecture: the dense block, each call recorded."""
import cell

_DENSE = cell.arch("dense_swiglu")
CALLS = []
KERNELS = {{"paged_attn": r"^%?spy_kernel\\.\\d+ "}}


def _recorded(name):
    def f(*a, **kw):
        CALLS.append(name)
        return getattr(_DENSE, name)(*a, **kw)
    return f


for _name in {INTERFACE!r}:
    globals()[_name] = _recorded(_name)
'''


def _window_trace():
    host = [("bench.window", 0, 100 * MS), ("bench.tick", 0, 100 * MS)]
    ops = [("%spy_kernel.4 = (f32[1]{0}) custom-call(%a)", 10 * MS,
            30 * MS),
           ("%paged_attention.11 = (f32[1]{0}) custom-call(%b)", 40 * MS,
            45 * MS)]
    return {"/device:TPU:0": ops}, host


def test_run_takes_everything_from_the_module(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(cell.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (bench / "arch" / "spy.py").write_text(SPY)
    monkeypatch.setattr(cell, "BENCH", bench)
    spy = cell.arch("spy")
    c = tiny.make(per_layer=("step_mfu", "paged_attn_roofline"))
    c.config["bench_arch"] = "spy"
    # a traced run whose trace is a synthetic one (the CPU has no device
    # plane): the kernel's time is what the spy's KERNELS credits
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(harness.tracing, "load", lambda d: _window_trace())
    res, rec = harness.run(c, 2 ** 31 + 3, 1.0, True, time.perf_counter(),
                           require_tpu=False)
    assert res["correct"], res
    assert rec["arch"] == "spy"
    assert rec["trace"]["kernel_s"] == {"paged_attn": pytest.approx(0.02)}
    assert set(res["metrics"]) == {"step_mfu", "paged_attn_roofline"}
    # weights, check, reference, work, pages and kernel names: the spy's
    assert set(INTERFACE) - {"prefill_flops"} <= set(spy.CALLS), spy.CALLS


def test_unknown_module_lists_those_there():
    with pytest.raises(SystemExit, match=r"no_such_block.*'dense_swiglu'"):
        cell.arch("no_such_block")


def test_configuration_must_name_its_module():
    cf = tiny.config()
    del cf["bench_arch"]
    with pytest.raises(SystemExit, match="bench_arch"):
        cell.model_config(cf)


def test_head_dim_from_the_file():
    dense = cell.arch("dense_swiglu")
    assert dense.dims(tiny.config())["dh"] == 64 // 4
    assert dense.dims(dict(tiny.config(), head_dim=32))["dh"] == 32
    # the program's block has head size 16: a file that says 32 is refused
    with pytest.raises(SystemExit, match="dense SwiGLU"):
        cell.model_config(dict(tiny.config(), head_dim=32))


def _files(*globs):
    return sorted(p for g in globs for p in cell.BENCH.glob(g))


SEAM = ("harness.py", "cell.py", "control.py", "run.py", "metrics/*.py",
        "traffic/*.py")


@pytest.mark.parametrize("path", _files(*SEAM), ids=lambda p: p.name)
def test_shared_files_reach_the_block_through_the_module(path):
    """No import of ``work`` or of a module under ``bench/arch/``, and no
    name that only an architecture module defines."""
    arches = {p.stem for p in (cell.BENCH / "arch").glob("*.py")}
    dense = cell.arch("dense_swiglu")
    own = {n for n, v in vars(dense).items()
           if callable(v) and getattr(v, "__module__", None) ==
           dense.__name__} - set(INTERFACE)
    assert own, "the dense module defines helpers of its own"
    tree = ast.parse(path.read_text())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        else:
            mods = []
        for mod in mods:
            assert mod.split(".")[0] not in {"work", "arch"} | arches, mod
        name = (node.id if isinstance(node, ast.Name) else
                node.attr if isinstance(node, ast.Attribute) else None)
        assert name not in own, (path.name, name)


BLOCK_WORDS = re.compile(r"\bwq\b|\bw1\b|\bmlp\b|tpu_custom_call|"
                         r"import work")


@pytest.mark.parametrize(
    "path", [p for p in _files("*.py", "*/*.py")
             if p.parent.name not in ("arch", "tests")],
    ids=lambda p: str(p.relative_to(cell.BENCH)))
def test_no_block_names_outside_the_modules(path):
    text = path.read_text()
    if path.name == "scopes.py":
        # the program's decode scopes, ``mlp`` among them, are the
        # program's names for every block's feed-forward, dense or expert
        cell.use_program()
        from repro.models.model import DECODE_SCOPES
        assert scopes.COMPUTE == DECODE_SCOPES
        text = re.sub(r"COMPUTE = \([^)]*\)", "", text)
    assert not BLOCK_WORDS.findall(text), BLOCK_WORDS.findall(text)
