"""Multi-device shard_map correctness (subprocess: needs
--xla_force_host_platform_device_count BEFORE jax init, which conftest
deliberately does not set — see the assignment's dry-run note)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_devprog(body: str, n_dev: int = 8):
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
        import jax, jax.numpy as jnp, numpy as np
        jax.config.update("jax_platform_name", "cpu")
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROC_OK")
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": SRC},
                       timeout=560)
    assert r.returncode == 0 and "SUBPROC_OK" in r.stdout, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"


@pytest.mark.slow
def test_itpp_sharded_matches_oracle():
    run_devprog("""
        from repro.core import paged_kv as PK, itpp as IT
        from repro.core.allocator import PageAllocator
        B, H, KVH, D, page, maxp, n_pages = 4, 8, 2, 16, 4, 8, 64
        alloc = PageAllocator(n_pages, 8, page, policy="striped")
        ctx_prev = np.array([13, 7, 22, 1], np.int32)
        bts = []
        for b in range(B):
            alloc.admit(b, int(ctx_prev[b]) + 1)
            bts.append(alloc.block_table(b, maxp))
        bt = jnp.asarray(np.stack(bts))
        key = jax.random.PRNGKey(0)
        pool_k = jax.random.normal(key, (n_pages, page, KVH, D))
        pool_v = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, KVH, D))
        q = jax.random.normal(jax.random.PRNGKey(2), (B, H, D))
        k_new = jax.random.normal(jax.random.PRNGKey(3), (B, KVH, D))
        v_new = jax.random.normal(jax.random.PRNGKey(4), (B, KVH, D))
        ctx = jnp.asarray(ctx_prev + 1)
        npage = jnp.asarray([bts[b][int(ctx_prev[b]) // page] for b in range(B)])
        noff = jnp.asarray(ctx_prev % page)
        pk_ref, pv_ref = PK.write_token(pool_k, pool_v, k_new, v_new, npage, noff)
        ref = PK.paged_decode_attention_ref(q, pk_ref, pv_ref, bt, ctx)
        mesh = make_mesh((8,), ("model",))
        spec = IT.ItppSpec(("model",), ("model",), None, 8, 8, page)
        f = IT.make_itpp_attention(mesh, spec, max_pages_per_req=maxp)
        out, pk, pv = jax.jit(f)(q, k_new, v_new, pool_k, pool_v, bt, ctx,
                                 npage, noff, 0)
        assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 1e-5
        assert np.abs(np.asarray(pk) - np.asarray(pk_ref)).max() == 0
    """)


@pytest.mark.slow
def test_itpp_sharded_stacked_pool_in_place():
    """The sharded op over the whole stacked [L, P, ...] pool with a traced
    ``layer``: layer 1's pages are read and written in place on each shard,
    the idle slot's dropped write lands nowhere, and the other layers come
    back bit-identical."""
    run_devprog("""
        from repro.core import paged_kv as PK, itpp as IT
        from repro.core.allocator import PageAllocator
        B, H, KVH, D, page, maxp, n_pages, L = 4, 8, 2, 16, 4, 8, 64, 3
        alloc = PageAllocator(n_pages, 8, page, policy="striped")
        ctx_prev = np.array([13, 7, 22, 0], np.int32)
        bts = []
        for b in range(B - 1):
            alloc.admit(b, int(ctx_prev[b]) + 1)
            bts.append(alloc.block_table(b, maxp))
        bts.append(np.full(maxp, -1, np.int32))               # idle slot
        bt = jnp.asarray(np.stack(bts))
        shape = (L, n_pages, page, KVH, D)
        pool_k = jax.random.normal(jax.random.PRNGKey(0), shape)
        pool_v = jax.random.normal(jax.random.PRNGKey(1), shape)
        q = jax.random.normal(jax.random.PRNGKey(2), (B, H, D))
        k_new = jax.random.normal(jax.random.PRNGKey(3), (B, KVH, D))
        v_new = jax.random.normal(jax.random.PRNGKey(4), (B, KVH, D))
        ctx = jnp.asarray(ctx_prev + 1)
        npage = jnp.asarray([bts[b][int(ctx_prev[b]) // page]
                             for b in range(B - 1)] + [n_pages])
        noff = jnp.asarray(ctx_prev % page)
        pk1, pv1 = PK.write_token(pool_k[1], pool_v[1], k_new, v_new, npage,
                                  noff)
        ref = PK.paged_decode_attention_ref(q, pk1, pv1, bt, ctx)
        mesh = make_mesh((8,), ("model",))
        spec = IT.ItppSpec(("model",), ("model",), None, 8, 8, page)
        f = IT.make_itpp_attention(mesh, spec, max_pages_per_req=maxp)
        out, pk, pv = jax.jit(f)(q, k_new, v_new, pool_k, pool_v, bt, ctx,
                                 npage, noff, 0, layer=jnp.int32(1))
        assert pk.shape == pv.shape == shape
        err = np.abs(np.asarray(out)[:B - 1] - np.asarray(ref)[:B - 1]).max()
        assert err < 1e-5, err
        for got, was, one in ((pk, pool_k, pk1), (pv, pool_v, pv1)):
            assert (np.asarray(got[1]) == np.asarray(one)).all()
            for l in (0, 2):
                assert (np.asarray(got[l]) == np.asarray(was[l])).all()
    """)


@pytest.mark.slow
def test_moe_ep_matches_local():
    run_devprog("""
        from dataclasses import replace
        from repro.configs import get_config, reduced
        from repro.models import moe as M
        from jax.sharding import PartitionSpec as P
        cfg = replace(reduced(get_config("mixtral-8x22b")), dtype="float32",
                      capacity_factor=8.0)   # dropless so paths agree
        V = 8   # 4 experts x 2 ff-slices on 8 shards
        p = M.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32, n_virtual=V)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
        y_local, aux_l = M.moe_local(p, cfg, x)
        mesh = make_mesh((8,), ("model",))
        def body(pw, x_loc):
            B, S, D = x_loc.shape
            y, aux = M.moe_ep(pw, cfg, x_loc.reshape(-1, D), "model", 8)
            return y.reshape(B, S, D), jax.lax.pmean(aux, "model")
        pspec = {"router": P(None, None), "w1": P("model", None, None),
                 "w2": P("model", None, None), "w3": P("model", None, None)}
        f = shard_map(body, mesh=mesh,
                      in_specs=(pspec, P(None, "model", None)),
                      out_specs=(P(None, "model", None), P()),
                      check_vma=False)
        y_ep, aux_e = jax.jit(f)({k: p[k] for k in pspec}, x)
        err = np.abs(np.asarray(y_ep) - np.asarray(y_local)).max()
        assert err < 1e-4, err
    """)


@pytest.mark.slow
def test_long_context_single_request_spans_all_shards():
    """long_500k layout: batch=1, pages striped over the whole mesh, merge
    over every axis — the paper's one-request-across-the-pod scenario."""
    run_devprog("""
        from repro.core import paged_kv as PK, itpp as IT
        from repro.core.allocator import PageAllocator
        B, H, KVH, D, page, maxp, n_pages = 1, 4, 1, 16, 4, 16, 64
        alloc = PageAllocator(n_pages, 8, page, policy="striped")
        ctx_prev = 57
        alloc.admit(0, ctx_prev + 1)
        bt = jnp.asarray(alloc.block_table(0, maxp)[None])
        key = jax.random.PRNGKey(0)
        pool_k = jax.random.normal(key, (n_pages, page, KVH, D))
        pool_v = jax.random.normal(jax.random.PRNGKey(1), (n_pages, page, KVH, D))
        q = jax.random.normal(jax.random.PRNGKey(2), (B, H, D))
        k_new = jax.random.normal(jax.random.PRNGKey(3), (B, KVH, D))
        v_new = jax.random.normal(jax.random.PRNGKey(4), (B, KVH, D))
        ctx = jnp.asarray([ctx_prev + 1])
        npage = jnp.asarray([alloc.block_table(0, maxp)[ctx_prev // page]])
        noff = jnp.asarray([ctx_prev % page])
        pk_ref, pv_ref = PK.write_token(pool_k, pool_v, k_new, v_new, npage, noff)
        ref = PK.paged_decode_attention_ref(q, pk_ref, pv_ref, bt, ctx)
        mesh = make_mesh((2, 4), ("data", "model"))
        spec = IT.ItppSpec(("data", "model"), ("data", "model"), None, 8, 8, page)
        f = IT.make_itpp_attention(mesh, spec, max_pages_per_req=maxp)
        out, _, _ = jax.jit(f)(q, k_new, v_new, pool_k, pool_v, bt, ctx,
                               npage, noff, 0)
        assert np.abs(np.asarray(out) - np.asarray(ref)).max() < 1e-5
    """)


@pytest.mark.slow
def test_sharded_prefill_writer_matches_global():
    """Blocked page allocation + shard-local prefill scatter (§Perf P1) must
    produce the identical pool as the global reference writer."""
    run_devprog("""
        from repro.core import paged_kv as PK, itpp as IT
        from repro.core.allocator import PageAllocator
        # production layout: pool pages sharded over (data, model) = 8,
        # requests row-affine to data rows, blocked striping over the row's
        # model shards so the seq-sharded writes stay local
        B, S, page, KVH, D = 2, 32, 4, 2, 8
        maxp = S // page
        stripe = 4                 # model axis size
        chunk = maxp // stripe
        alloc = PageAllocator(32, 8, page, policy="row_affine", n_rows=2,
                              blocked_chunk=chunk)
        bts = []
        for b in range(B):
            alloc.admit(b, S, row=b)
            bts.append(alloc.block_table(b, maxp))
        bt = jnp.asarray(np.stack(bts))
        key = jax.random.PRNGKey(0)
        k = jax.random.normal(key, (B, S, KVH, D))
        v = jax.random.normal(jax.random.PRNGKey(1), (B, S, KVH, D))
        pool_k = jnp.zeros((32, page, KVH, D))
        pool_v = jnp.zeros((32, page, KVH, D))
        ref_k, ref_v = PK.write_prefill(pool_k, pool_v, k, v, bt)
        mesh = make_mesh((2, 4), ("data", "model"))
        spec = IT.ItppSpec(("data", "model"), ("model",), "data", 8, 4, page)
        writer = IT.make_prefill_writer(mesh, spec, seq_axis="model")
        out_k, out_v = jax.jit(writer)(pool_k, pool_v, k, v, bt)
        assert np.abs(np.asarray(out_k) - np.asarray(ref_k)).max() == 0
        assert np.abs(np.asarray(out_v) - np.asarray(ref_v)).max() == 0
    """)


@pytest.mark.slow
def test_pp_decode_matches_forward():
    """GPipe decode over the pod axis (nested ITPP+TP inside partial-manual
    shard_map) must equal the plain full-sequence forward."""
    run_devprog("""
        from dataclasses import replace
        from repro.configs import get_config, reduced, ParallelConfig, ShapeConfig
        from repro.core.allocator import PageAllocator
        from repro.core.paged_kv import PoolSpec
        from repro.distributed.sharding import make_plan
        from repro.distributed.pipeline import make_pp_decode_step
        from repro.models import model as MDL
        cfg = replace(reduced(get_config("llama3.2-1b"), layers=4),
                      dtype="float32")
        params = MDL.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        B, S, page, mbs = 4, 12, 4, 2
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab_size)
        logits_ref, _ = MDL.forward(cfg, params, toks)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        shape = ShapeConfig("d", "decode", S, B)
        parallel = ParallelConfig(dp=2, tp=2, pods=2, page_size=page)
        plan = make_plan(mesh, parallel, shape, pod_mode="pp")
        maxp = S // page + 1
        pool = PoolSpec(cfg.n_layers, 16, page, cfg.n_kv_heads, cfg.d_head,
                        maxp, dtype="float32")
        state = MDL.init_decode_state(cfg, pool, B, dtype="float32")
        alloc = PageAllocator(16, 4, page, policy="row_affine", n_rows=2)
        bts = []
        for b in range(B):
            alloc.admit(b, S, row=b % 2)   # request b -> data shard b % mb
            bts.append(alloc.block_table(b, maxp))
        bt = np.stack(bts)
        S_pre = 8
        _, state = MDL.prefill(cfg, params, state, toks[:, :S_pre],
                               jnp.asarray(bt))
        step = make_pp_decode_step(cfg, plan, parallel, pool, n_stages=2,
                                   microbatches=mbs)
        jstep = jax.jit(step)
        for t in range(S_pre, S):
            batch = {"tokens": toks[:, t], "bt": jnp.asarray(bt),
                     "ctx": jnp.full((B,), t + 1, jnp.int32),
                     "npage": jnp.asarray([bts[b][t // page]
                                           for b in range(B)]),
                     "noff": jnp.full((B,), t % page, jnp.int32)}
            lg, state = jstep(params, state, batch)
            err = np.abs(np.asarray(lg)
                         - np.asarray(logits_ref[:, t])).max()
            assert err < 5e-3, (t, err)
    """)


@pytest.mark.slow
def test_train_step_sharded_matches_single_device():
    """FSDP-sharded train step == single-device train step (same batch)."""
    run_devprog("""
        from dataclasses import replace
        from repro.configs import get_config, reduced, ParallelConfig, SHAPES, ShapeConfig
        from repro.distributed.sharding import make_plan
        from repro.models import model as MDL
        from repro.training import optimizer as OPT
        from repro.training.train import make_train_step
        cfg = replace(reduced(get_config("llama3.2-1b")), dtype="float32")
        params = MDL.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        B, S = 8, 16
        batch = {
          "tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size),
          "targets": jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size),
          "mask": jnp.ones((B, S), jnp.float32)}
        opt_cfg = OPT.AdamWConfig(lr=1e-3)
        ref_step = jax.jit(make_train_step(cfg, MDL.DEFAULT_RT, opt_cfg))
        p_ref, _, m_ref = ref_step(params, OPT.init(params), batch)
        mesh = make_mesh((2, 4), ("data", "model"))
        shp = ShapeConfig("t", "train", S, B)
        plan = make_plan(mesh, ParallelConfig(dp=2, tp=4), shp)
        rt = plan.make_runtime(cfg, ParallelConfig(remat=False), mode="train")
        step = make_train_step(cfg, rt, opt_cfg)
        pspec = plan.param_specs(params, mode="train")
        in_sh = (plan.named(pspec),
                 plan.named({"m": pspec, "v": pspec,
                             "step": jax.sharding.PartitionSpec()}),
                 None)
        jstep = jax.jit(step, in_shardings=in_sh)
        p_sh, _, m_sh = jstep(params, OPT.init(params), batch)
        assert abs(float(m_sh["loss"]) - float(m_ref["loss"])) < 1e-4
        d = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_sh)))
        assert d < 1e-4, d
    """)
