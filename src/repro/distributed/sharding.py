"""Sharding plans: parameter PartitionSpecs, activation constraints, and the
Runtime wiring (ITPP decode attention + expert-parallel MoE) per cell.

Two weight layouts (DESIGN.md §4):

* ``train`` — FSDP/ZeRO-3: every large leaf sharded over (dp..., model) on
  its last two divisible dims; compute is data/sequence-parallel ("sp" mode:
  batch over the data axes, sequence over the model axis) with weights
  gathered per layer by XLA. Works for every arch regardless of head counts —
  the same argument the paper makes for token-parallel over head-first.
* ``serve`` — Megatron TP resident weights: column-parallel up/QKV,
  row-parallel down/out over the model axis; the batch rides the data axes
  as independent serving rows; attention is ITPP (pages sharded over
  dp+model, stable merge). MoE weights live in virtual-expert layout with
  the expert dim on the model axis (EP).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.core.itpp import ItppSpec, make_itpp_attention
from repro.models.model import Runtime, mixed_window
from repro.models import moe as MOE

STACKED_KEYS = {"layers", "enc", "dec", "mamba", "mlstm", "slstm"}
# serve-mode column-parallel (shard last dim) / row-parallel (shard first
# non-stack dim) weight names
COL_NAMES = {"wq", "wk", "wv", "w1", "w3", "wz", "wx", "wu", "wg"}
ROW_NAMES = {"wo", "w2", "out_proj", "down"}
REPLICATE_SMALL = 1 << 16


def abstract_mesh(shape, axis_names):
    """A device-free mesh (tests and dry-run tooling build meshes through
    this so plan invariants can be checked without real devices)."""
    return AbstractMesh(tuple(shape), tuple(axis_names))


def _path_keys(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
    return out


@dataclass
class Plan:
    mesh: Any
    dp_axes: tuple[str, ...]          # ('data',) or ('pod','data')
    tp_axis: str                      # 'model'
    shape_kind: str                   # train | prefill | decode
    batch_divisible: bool             # global_batch % prod(dp_axes) == 0
    seq_divisible: bool = True
    pod_mode: str = "dp"
    # train/prefill activation layout:
    #  'fsdp' — batch sharded over EVERY mesh axis, sequence local: no KV
    #           gathers, weights gathered per layer (ZeRO-3). Chosen when
    #           global_batch divides the device count.
    #  'sp'   — batch over dp axes, sequence over the model axis (context
    #           parallelism): K/V all-gathered per layer. Chosen otherwise.
    train_layout: str = "fsdp"

    # -------------------- sizes --------------------
    @property
    def axis_sizes(self) -> dict[str, int]:
        return dict(self.mesh.shape)

    @property
    def dp_total(self) -> int:
        s = self.axis_sizes
        return int(np.prod([s[a] for a in self.dp_axes]))

    @property
    def tp(self) -> int:
        return self.axis_sizes[self.tp_axis]

    @property
    def dp_spec(self):
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    @property
    def batch_spec(self):
        return self.dp_spec if self.batch_divisible else None

    # -------------------- activation constraints --------------------
    @property
    def full_batch_spec(self):
        return (*self.dp_axes, self.tp_axis)

    def _act_table(self) -> dict[str, P]:
        dp, tp, b = self.dp_spec, self.tp_axis, self.batch_spec
        if self.train_layout == "fsdp":
            fb = self.full_batch_spec
            return {
                "act": P(fb, None, None),
                "kv_full": P(fb, None, None, None),
                "logits": P(fb, None, None),
                "act_decode": P(b, None),
                "logits_decode": P(b, tp),
            }
        seq = tp if self.seq_divisible else None
        return {
            "act": P(dp, seq, None),
            "kv_full": P(dp, None, None, None),
            "logits": P(dp, seq, None),
            "act_decode": P(b, None),
            "logits_decode": P(b, tp),
        }

    def constrain(self, x, name: str):
        spec = self._act_table().get(name)
        if spec is None:
            return x
        spec = P(*spec[: x.ndim])
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, spec))

    # -------------------- parameter specs --------------------
    def param_specs(self, params, *, mode: str):
        """mode: 'train' (FSDP) or 'serve' (Megatron TP, rows replicated)."""
        sizes = self.axis_sizes
        dp_n, tp_n = self.dp_total, self.tp
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        specs = []
        for path, leaf in flat:
            keys = _path_keys(path)
            stacked = keys[0] in STACKED_KEYS
            name = keys[-1]
            if name in ("q", "s") and len(keys) >= 2:   # int8 QTensor leaves
                name = keys[-2]
            shape = leaf.shape
            dims = list(shape)
            spec = [None] * len(dims)
            start = 1 if stacked else 0
            body = dims[start:]
            is_moe = "moe" in keys
            if int(np.prod(body or [1])) < REPLICATE_SMALL:
                specs.append(P())
                continue
            if mode == "serve":
                if is_moe and name in ("w1", "w2", "w3"):
                    # [*, V, D, ffv] — virtual experts on the model axis (EP)
                    spec[start] = self.tp_axis
                elif name == "embed":
                    spec[1] = self.tp_axis          # [V, D] shard D
                elif name == "head":
                    spec[1] = self.tp_axis          # [D, V] vocab col-TP
                elif name in COL_NAMES and len(shape) - start == 2:
                    if shape[-1] % tp_n == 0:
                        spec[-1] = self.tp_axis
                elif name in ROW_NAMES and len(shape) - start == 2:
                    if shape[start] % tp_n == 0:
                        spec[start] = self.tp_axis
                specs.append(P(*spec))
                continue
            # ---- train: FSDP over (dp, model) on last two divisible dims
            if is_moe and name in ("w1", "w2", "w3"):
                spec[start] = self.tp_axis          # EP entry layout
                if shape[start + 1] % dp_n == 0:
                    spec[start + 1] = self.dp_spec
                specs.append(P(*spec))
                continue
            if name == "embed":
                spec[1] = self.tp_axis
                if shape[0] % dp_n == 0:
                    spec[0] = self.dp_spec
                specs.append(P(*spec))
                continue
            if shape[-1] % tp_n == 0 and len(shape) - start >= 1:
                spec[-1] = self.tp_axis
            if len(shape) - start >= 2 and shape[-2] % dp_n == 0:
                spec[-2] = self.dp_spec
            specs.append(P(*spec))
        return jax.tree_util.tree_unflatten(treedef, specs)

    def named(self, spec_tree):
        return jax.tree.map(
            lambda s: jax.sharding.NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    # -------------------- decode state specs --------------------
    @property
    def page_axes(self) -> tuple[str, ...]:
        return (*self.dp_axes, self.tp_axis)

    def pool_spec(self):
        return P(None, self.page_axes, None, None, None)

    def decode_state_specs(self, state):
        b = self.batch_spec
        out = {}
        for k, v in state.items():
            if k == "pool":
                out[k] = {"k": self.pool_spec(), "v": self.pool_spec()}
            elif k in ("cross_k", "cross_v"):
                out[k] = P(None, b, *([None] * (v.ndim - 2)))
            else:   # recurrent states: [n, B, ...]
                out[k] = jax.tree.map(
                    lambda a: P(None, b, *([None] * (a.ndim - 2))), v)
        return out

    # -------------------- runtime wiring --------------------
    def itpp_spec(self, page_size: int) -> ItppSpec:
        sizes = self.axis_sizes
        n_page_shards = int(np.prod([sizes[a] for a in self.page_axes]))
        if self.batch_divisible:
            # requests pinned to data rows; stripe over the row's model shards
            return ItppSpec(self.page_axes, (self.tp_axis,), self.batch_spec,
                            n_page_shards, self.tp, page_size)
        # batch replicated: stripe each request over the whole mesh
        return ItppSpec(self.page_axes, self.page_axes, None,
                        n_page_shards, n_page_shards, page_size)

    def make_runtime(self, cfg, parallel, *, pool_spec=None,
                     mode: str = "train") -> Runtime:
        from repro.kernels.backend import KernelConfig
        kernels = KernelConfig(use_pallas=parallel.use_pallas,
                               interpret=parallel.kernel_interpret,
                               n_splits=parallel.kernel_splits)
        rt = Runtime(constrain=self.constrain, remat=parallel.remat,
                     kernels=kernels)
        if pool_spec is not None:
            rt.ring_width = pool_spec.max_pages_per_req if pool_spec.ring else 0
            if mode == "decode":
                spec = self.itpp_spec(parallel.page_size)
                rt.cond_window = mixed_window(cfg)
                rt.itpp = make_itpp_attention(
                    self.mesh, spec,
                    max_pages_per_req=pool_spec.max_pages_per_req,
                    ring_width=rt.ring_width,
                    cond_window=rt.cond_window,
                    kernels=kernels)
            if mode == "prefill" and not pool_spec.ring \
                    and self.train_layout == "sp" and self.seq_divisible:
                from repro.core.itpp import make_prefill_writer
                rt.write_pool = make_prefill_writer(
                    self.mesh, self.itpp_spec(parallel.page_size),
                    seq_axis=self.tp_axis)
        if cfg.is_moe:
            rt.moe = self._make_moe_ep(cfg)
        return rt

    def _make_moe_ep(self, cfg):
        mesh, tp_axis, tp_n = self.mesh, self.tp_axis, self.tp
        dp, b = self.dp_spec, self.batch_spec
        seq = tp_axis if self.seq_divisible else None

        def body(pw, x_loc):
            B, S, D = x_loc.shape
            y, aux = MOE.moe_ep(pw, cfg, x_loc.reshape(-1, D), tp_axis, tp_n)
            return y.reshape(B, S, D), jax.lax.pmean(
                aux, (*self.dp_axes, tp_axis))

        def apply(p, cfg_, x):
            is_decode = x.shape[1] == 1
            act = self._act_table()["act"]
            xspec = P(b, None, None) if is_decode else P(*act[:2], None)
            pspec = {"router": P(None, None),
                     "w1": P(tp_axis, None, None),
                     "w2": P(tp_axis, None, None)}
            if "w3" in p:
                pspec["w3"] = P(tp_axis, None, None)
            fn = shard_map(
                body, mesh=mesh, in_specs=(pspec, xspec),
                out_specs=(xspec, P()), check_vma=False)
            return fn({k: p[k] for k in pspec}, x)

        return apply


def make_plan(mesh, parallel, shape, *, pod_mode: str = "dp",
              train_layout: str | None = None) -> Plan:
    axes = mesh.axis_names
    dp_axes = tuple(a for a in axes if a in ("pod", "data")) \
        if pod_mode == "dp" else ("data",)
    sizes = dict(mesh.shape)
    dp_total = int(np.prod([sizes[a] for a in dp_axes]))
    tp = sizes["model"]
    n_dev = int(np.prod(list(sizes.values())))
    if train_layout is None:
        train_layout = "fsdp" if shape.global_batch % n_dev == 0 else "sp"
    return Plan(
        mesh=mesh,
        dp_axes=dp_axes,
        tp_axis="model",
        shape_kind=shape.kind,
        batch_divisible=shape.global_batch % dp_total == 0,
        seq_divisible=(shape.seq_len % tp == 0) and shape.kind != "decode",
        pod_mode=pod_mode,
        train_layout=train_layout,
    )
