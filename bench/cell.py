"""Finding a cell's files by name, and building the served model and
engine settings from a configuration file.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own under ``bench/``, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the published config keys, as run, with
  the engine settings, what was assumed, and ``bench_arch``, the name of
  its architecture module;
- ``bench/arch/<arch>.py``: everything that depends on the block: the
  check that the program serves it (``check``), the model dims (``dims``),
  the served weights (``make``), the float32 reference's hidden states
  (``hidden``), the work of the algorithm (``decode_flops``,
  ``prefill_flops``, ``kernel_work``, ``step_bytes``, ``session_pages``)
  and the kernels' names in the device trace (``KERNELS``);
- ``bench/traffic/<mix>.json``: a traffic mix's parameters, with the name
  of its kind;
- ``bench/traffic/<kind>.py``: a traffic kind, with ``requests``, ``setup``
  and ``drive`` (see ``bench/generator.py``);
- ``bench/metrics/<metric>.py``: a reader with ``read(rec) -> float | None``;
- ``bench/limits/<cell>.json``: the correctness limit of a cell and the
  readings it was set from.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import replace
from functools import cache
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
SRC = REPO / "src"


def use_program() -> None:
    """Make the program (``src/repro``) importable."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(REPO / "BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def config_file(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic_file(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits_file(cell: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell}.json")


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    return _module(BENCH / "metrics" / f"{name}.py",
                   f"bench_metric_{name}").read


def traffic_kind(name: str):
    """The module ``bench/traffic/<name>.py`` of a traffic kind."""
    return _module(BENCH / "traffic" / f"{name}.py", f"bench_kind_{name}")


def metrics_for(bm: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports:
    those that list it under ``workloads``, or list no cells at all."""
    return [m for m in bm[kind]
            if "workloads" not in m or cell in m["workloads"]]


@cache
def _arch_at(path: Path, name: str):
    return _module(path, f"bench_arch_{name}")


def arch(name: str):
    """The architecture module ``bench/arch/<name>.py``, loaded once."""
    path = BENCH / "arch" / f"{name}.py"
    if not path.is_file():
        have = sorted(p.stem for p in (BENCH / "arch").glob("*.py"))
        raise SystemExit(f"bench: no architecture module {name!r}; "
                         f"bench/arch/ holds {have}")
    return _arch_at(path, name)


def arch_of(cf: dict):
    """The architecture module that configuration file ``cf`` names."""
    if "bench_arch" not in cf:
        raise SystemExit(f"bench: {cf['name']}: the configuration file "
                         f"names no bench_arch (a module under bench/arch/)")
    return arch(cf["bench_arch"])


def model_config(cf: dict):
    """The program's ModelConfig for configuration file ``cf``: the
    registered architecture at its published widths, cut to the file's
    depth, with the file's norm epsilon, rope base and dtype. Raises where
    the file's architecture module finds that the program does not serve
    its block at the file's widths."""
    use_program()
    from repro.launch import serve
    # ``program_full`` false takes the architecture's reduced float32
    # twin: tiny widths for tests on the CPU
    cfg = serve.model_config(cf["program_arch"], cf.get("program_full", True),
                             layers=cf["num_hidden_layers"])
    cfg = replace(cfg, norm_eps=float(cf["rms_norm_eps"]),
                  rope_theta=float(cf["rope_theta"]),
                  dtype=cf["torch_dtype"],
                  tie_embeddings=bool(cf["tie_word_embeddings"]))
    arch_of(cf).check(cf, cfg)
    return cfg


def engine_config(cf: dict, **over):
    """The EngineConfig of configuration file ``cf``: its ``engine``
    settings, greedy sampling and no end-of-sequence token (requests run
    to their budgets), the decode kernel chosen by the backend."""
    use_program()
    from repro.serving import EngineConfig
    e = dict(cf["engine"])
    e.update(over)
    return EngineConfig(
        n_slots=e["n_slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], max_context=e["max_context"],
        decode_horizon=e["decode_horizon"], prefill_mode=e["prefill_mode"],
        prefix_cache=e["prefix_cache"], eos_token=-1, sampler="greedy",
        use_pallas=e.get("use_pallas"),
        kernel_interpret=e.get("kernel_interpret"))
