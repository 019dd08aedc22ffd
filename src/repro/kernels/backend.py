"""Backend autodetection + the KernelConfig threaded through the stack.

One knob object rides from ``configs.ParallelConfig`` / ``launch.serve``
through ``models.model.Runtime`` down to the Pallas call sites
(``kernels/ops.py``, ``core/itpp.py``): *which* compute path serves the
decode hot path and *how* the kernels execute.

Resolution rules (``KernelConfig.resolve``):

* ``use_pallas=None``  -> True on a TPU backend, False elsewhere (the
  pure-jnp reference math IS the production path off-TPU — identical
  semantics, tested);
* ``interpret=None``   -> False on TPU (compile via Mosaic), True elsewhere
  (Pallas interpret mode for correctness tests on CPU). Nothing else
  reaches it: on TPU a kernel runs interpreted only when its caller passes
  ``interpret=True``.

The dataclass is frozen/hashable so it can ride as a jit static argument
and through ``functools.partial`` into shard_map bodies.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """interpret=False on TPU, True elsewhere."""
    return not on_tpu()


def resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


@dataclass(frozen=True)
class KernelConfig:
    """How the decode hot path executes (see module docstring).

    ``n_splits``: split-K partitions of the page axis inside one kernel
    call — the intra-chip analogue of the paper's TCP token split (shards
    are the inter-chip one). 1 = online-softmax over all pages in a single
    sequential pass.
    """
    use_pallas: bool | None = None
    interpret: bool | None = None
    n_splits: int = 1

    def resolve(self) -> "KernelConfig":
        return KernelConfig(
            use_pallas=on_tpu() if self.use_pallas is None else
            bool(self.use_pallas),
            interpret=resolve_interpret(self.interpret),
            n_splits=max(1, int(self.n_splits)))


DEFAULT_KERNELS = KernelConfig()


def decode_hbm_bytes(ctx_tokens: float, n_kv_heads: int, d_head: int,
                     bytes_per_el: int, n_layers: int = 1) -> float:
    """Modeled KV bytes one decode step streams from HBM for a request at
    context ``ctx_tokens``: K and V read once across the live context. The
    hot-path ideal the paged kernels approach (a dense gather reads the
    full table width instead) — used by ``benchmarks/kernel_bench`` for the
    offline MB/token report and by ``telemetry.pim_counters`` for the same
    quantity live during serving."""
    return 2.0 * ctx_tokens * n_kv_heads * d_head * bytes_per_el * n_layers
