"""The program-name reductions of a trace (``bench/scopes.py``) on
synthetic event lists, and the three readers that use them."""
import cell
import scopes

MS = 1_000_000


def _op(name, s, e, module=scopes.DECODE, scope=None):
    return (name, s * MS, e * MS, module, scope)


def _host():
    # window 0-100 ms; two ticks, the second with the engine's phases
    return [("bench.window", 0, 100 * MS),
            ("bench.tick", 10 * MS, 40 * MS),
            ("bench.tick", 50 * MS, 90 * MS),
            ("engine.overlap", 50 * MS, 55 * MS),
            ("engine.collect", 55 * MS, 80 * MS),
            ("engine.dispatch", 82 * MS, 88 * MS)]


def _device():
    return {"/device:TPU:0": [
        _op("%fusion.1 = bf16[8]{0} fusion(%a)", 0, 20, scope="mlp"),
        _op("%closed_call.3 = (f32[1]{0}) custom-call(%b), "
            'custom_call_target="tpu_custom_call"', 20, 30,
            scope="paged_attn"),
        _op("%copy.83 = bf16[4]{0} copy(%c)", 30, 45),
        _op("%dynamic-slice_bitcast_fusion.5 = bf16[4]{0} fusion(%d)",
            60, 70, scope="layers"),
        _op("%fusion.9 = s32[2]{0} fusion(%e)", 70, 72,
            module="jit_patch_slots"),
        # control flow spans the ops it runs: left out
        _op("%while.2 = (s32[]) while(%t)", 0, 100),
    ]}


def test_module_and_scope_seconds():
    r = scopes.reduce(_device(), _host())
    assert r["module_s"] == {scopes.DECODE: 0.055, "jit_patch_slots": 0.002}
    assert r["scope_s"] == {"mlp": 0.02, scopes.UNSCOPED: 0.015,
                            "paged_attn": 0.01, "layers": 0.01}
    assert [o[:2] for o in r["decode_ops"]][:2] == [
        ["fusion.1 fusion bf16[8]", "mlp"], ["copy.83 copy bf16[4]",
                                             scopes.UNSCOPED]]


def test_gap_under_engine_span_is_named_by_it():
    """Idle 45-60 ms lies under bench.tick 50-90 and, from 55, under
    engine.collect nested in it: the gap's middle (52.5) is in
    engine.overlap; the 72-100 gap's middle (86) in engine.dispatch."""
    r = scopes.reduce(_device(), _host())
    assert r["idle_gaps"] == [["engine.dispatch", 0.028],
                              ["engine.overlap", 0.015]]


def test_idle_split_across_covering_spans():
    r = scopes.reduce(_device(), _host())
    # 45-60: 45-50 under no span, 50-55 overlap, 55-60 collect;
    # 72-100: 72-80 collect, 80-82 tick, 82-88 dispatch, 88-90 tick,
    # 90-100 no span
    assert r["idle_by_span"] == {
        "bench.window (no span)": 0.015, "engine.collect": 0.013,
        "engine.dispatch": 0.006, "engine.overlap": 0.005,
        "bench.tick": 0.004}
    total = sum(r["idle_by_span"].values())
    assert abs(total - sum(g for _, g in r["idle_gaps"])) < 1e-12


def test_op_scopes_from_hlo_text():
    text = "\n".join([
        '  %fusion.180 = bf16[16,2048]{1,0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(decode_horizon)/while/body/closed_call/'
        'layers/while/body/closed_call/mlp/dot_general"}',
        '  ROOT %paged_attention.1 = (f32[1]) custom-call(%q), '
        'metadata={op_name="jit(decode_horizon)/while/body/layers/while/'
        'body/paged_attn/paged_attention/pallas_call"}',
        '  %dynamic-slice_bitcast_fusion.5 = bf16[4] fusion(%x), '
        'metadata={op_name="jit(decode_horizon)/while/body/layers/while/'
        'body/dynamic_slice"}',
        '  %scatter.2 = bf16[4] scatter(%y), metadata={op_name='
        '"jit(decode_horizon)/layers/while/body/paged_attn/kv_write/'
        'scatter"}',
        '  %copy.83 = bf16[4] copy(%z)',
    ])
    assert scopes.op_scopes(text) == {
        "fusion.180": "mlp", "paged_attention.1": "paged_attn",
        "dynamic-slice_bitcast_fusion.5": "layers", "scatter.2": "kv_write"}
    assert scopes.op_id("%copy.83 = bf16[4]{0} copy(%z)") == "copy.83"


def test_readers_on_a_reduced_trace():
    rec = {"trace": scopes.reduce(_device(), _host()), "decode_steps": 11,
           "paged_grid_cells": 400, "paged_live_cells": 100}
    ms = cell.metric_reader("decode_device_ms_per_step")(rec)
    assert abs(ms - 55 / 11) < 1e-9
    mv = cell.metric_reader("decode_movement_frac")(rec)
    assert abs(mv - 0.025 / 0.055) < 1e-9
    assert cell.metric_reader("paged_grid_live_frac")(rec) == 0.25


def test_readers_without_a_trace_give_none():
    rec = {"trace": None, "decode_steps": 8}
    for name in ("decode_device_ms_per_step", "decode_movement_frac",
                 "paged_grid_live_frac"):
        assert cell.metric_reader(name)(rec) is None, name


def test_live_frac_of_a_tiny_window():
    """The engine's counters over a tiny cell's window (CPU): the live
    share of the paged grid lies in (0, 1]."""
    import harness
    import tiny
    from repro.serving import DecodeEngine
    c, seed = tiny.make(), 7
    cf = c.config
    cfg = cell.model_config(cf)
    arch = cell.arch_of(cf)
    m = arch.dims(cf, cfg.vocab_multiple)
    kind = cell.traffic_kind(c.traffic["kind"])
    eng = DecodeEngine(cfg, cell.engine_config(cf),
                       params=arch.make(m, seed, cf["torch_dtype"]))
    reqs = kind.requests(c.traffic, arch, m, cf["engine"], c.run_seconds,
                         seed)
    h = harness.Run(eng)
    kind.setup(h, reqs)
    t = eng.timing
    before = {k: getattr(t, k) for k in scopes.COUNTERS}
    h.open()
    kind.drive(h, reqs, 0.5)
    rec = {k: getattr(t, k) - before[k] for k in scopes.COUNTERS}
    assert rec["decode_steps"] > 0
    assert 0 < cell.metric_reader("paged_grid_live_frac")(rec) <= 1
