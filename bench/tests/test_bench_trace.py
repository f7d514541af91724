"""Trace reduction, the work counts and the table of peaks, on a
hand-built trace and on known shapes."""
import types

import numpy as np
import pytest

import benchfix  # noqa: F401  (puts the harness on the path)
from harness import counts, layers, registry
from harness import trace as T

# op names as the TPU profiler gives them: the HLO instruction
QLR = ("%mxint_lowrank_matmul.51 = f32[2,3072]{1,0} custom-call(f32[2,3072] "
       "%x), custom_call_target=\"tpu_custom_call\"")
PREFILL_QLR = ("%mxint_lowrank_matmul.9 = f32[4096,3072]{1,0} custom-call("
               "f32[4096,3072] %x), custom_call_target=\"tpu_custom_call\"")
USE = "%abs.16 = f32[2,3072]{1,0} abs(f32[2,3072]{1,0} %mxint_lowrank_matmul.51)"
ATTN = ("%_decode_attention_pallas_paged.7 = f32[2,32,1,96]{3,2,1,0} "
        "custom-call(s32[2,64] %a), custom_call_target=\"tpu_custom_call\"")

PHI3 = {"n_layers": 32, "d_model": 3072, "n_heads": 32, "n_kv_heads": 32,
        "head_dim": 96, "d_ff": 8192, "vocab": 32064}


def _ev(name, start_us, dur_us):
    return types.SimpleNamespace(name=name, start_ns=start_us * 1e3,
                                 duration_ns=dur_us * 1e3)


def _profile():
    line = lambda name, evs: types.SimpleNamespace(name=name, events=evs)
    plane = lambda name, lines: types.SimpleNamespace(name=name, lines=lines)
    tpu = plane("/device:TPU:0", [
        line("XLA Modules", [_ev("jit__decode(1)", 0, 100),
                             _ev("jit__decode(1)", 150, 100)]),
        line("XLA Ops", [_ev(QLR, 0, 30),
                         _ev(USE, 20, 40),      # overlaps the first
                         _ev(ATTN, 60, 40),
                         _ev(QLR, 150, 30),
                         _ev(ATTN, 180, 70),
                         _ev(PREFILL_QLR, 240, 0)]),
        line("Steps", [_ev("0", 0, 250)])])
    host = plane("/host:CPU", [line("python", [
        _ev("engine_step", 0, 140), _ev("decode_dispatch", 5, 10),
        _ev("engine_step", 140, 120), _ev("other", 0, 300)])])
    return types.SimpleNamespace(planes=[tpu, host])


def test_busy_union_gaps_and_kernel_time():
    tr = T.from_profile(_profile())
    ops = tr.ops[0]
    assert len(ops) == 6 and len(tr.modules[0]) == 2
    assert [h.name for h in tr.host] == ["engine_step", "decode_dispatch",
                                         "engine_step"]
    # busy: [0, 100) and [150, 250)
    assert T.busy_s(ops) == pytest.approx(200e-6)
    assert T.gaps(ops) == [pytest.approx((100e-6, 150e-6))]
    assert T.total_s(T.matching(ops, layers.qlr_decode_kernel(2))) == \
        pytest.approx(60e-6)
    assert len(T.matching(ops, layers.ATTN_KERNEL)) == 2
    top = T.top_ops(ops)
    assert top[0][0] == ATTN and top[0][1] == pytest.approx(110e-6)
    gaps = T.idle_by_host(T.gaps(ops), tr.host)
    assert gaps == [["engine_step", pytest.approx(50e-6)]]
    assert T.idle_by_host([(400e-6, 500e-6)], tr.host)[0][0] == \
        "outside engine_step"


def test_top_ops_leave_out_the_loop_around_them():
    loop = "%while.4 = (s32[], f32[32,1,3072]) while((s32[], f32[32,1,3072]))"
    ops = [T.Ev(loop, 0.0, 10.0), T.Ev(ATTN, 1.0, 6.0), T.Ev(QLR, 7.0, 2.0),
           T.Ev(ATTN, 12.0, 1.0), T.Ev(USE, 12.5, 1.0)]
    assert T.leaves(ops) == ops[1:]
    assert T.top_ops(ops) == [[ATTN, 7.0], [QLR, 2.0], [USE, 1.0]]
    assert T.busy_s(ops) == pytest.approx(11.5)


def test_kv_bytes_per_token_phi3_int4():
    # 32 layers x 32 kv heads x 96 x (K, V) at half a byte, plus an f32
    # scale per (layer, head, K/V)
    assert counts.kv_bytes_per_token(PHI3, "int4") == \
        32 * 32 * 96 + 32 * 32 * 2 * 4 == 106496
    assert counts.kv_bytes_per_token(PHI3, "bf16") == 2 * 32 * 32 * 96 * 2


def test_step_work_from_served_shapes():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import init_lm
    from repro.models.quantize import quantized_abstract
    cfg = get_config("phi3-mini-3.8b")
    ab = quantized_abstract(
        jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)), rank=16)
    w = counts.step_work(ab, PHI3, "int4")
    d, ff, L = 3072, 8192, 32
    mn = 4 * d * d + 3 * d * ff                  # wq wk wv wo + up gate down
    assert w.qlr_macs_per_row == L * (mn + 16 * (4 * (d + d)
                                                 + 2 * (d + ff) + (ff + d)))
    codes, scales = L * mn, L * mn // 32 * 4
    lr = L * 16 * 4 * (4 * 2 * d + 2 * (d + ff) + (ff + d))
    assert w.qlr_weight_bytes == codes + scales + lr
    assert 4.0e9 < w.qlr_weight_bytes < 4.3e9    # 3.62 + 0.45 + 0.12 GB
    assert w.head_macs_per_row == d * 32064
    assert w.dense_weight_bytes == 4 * (d * 32064 + d + 2 * L * d)
    assert w.attn_macs_per_ctx == 2 * L * 32 * 96
    del jnp


def test_roofline_picks_the_binding_bound():
    t, bound = counts.roofline_s(1e12, 1e9, 1e14, 1e12)
    assert (t, bound) == (pytest.approx(1e-2), "compute")
    t, bound = counts.roofline_s(1e9, 1e9, 1e14, 1e12)
    assert (t, bound) == (pytest.approx(1e-3), "memory")


def test_kernel_share_and_step_metrics_on_a_known_trace():
    tr = T.from_profile(_profile())
    w = counts.StepWork(qlr_weight_bytes=7 * 2 * 1000, qlr_macs_per_row=10,
                        qlr_io_per_row=0, dense_weight_bytes=0,
                        head_macs_per_row=0, kv_bytes_per_token=10,
                        attn_macs_per_ctx=0)
    run = layers.TracedRun(
        trace=tr, window_s=300e-6,
        decode_calls=[layers.DecodeCall(live=2, ctx=100)] * 2, work=w,
        model={"n_layers": 2}, lanes=2,
        peaks={"bf16_flops": 1e15, "hbm_bytes_per_s": 1e9}, counters={},
        gen_lags_s=[])
    # 2 decode Q+LR calls (the prefill one is left out) of 1000 bytes each at 1 GB/s = 2 us over 60 us
    assert layers.qlr_roofline(run) == pytest.approx(100 * 2e-6 / 60e-6)
    # attention: per call 10 B/token x 100 ctx / 2 layers = 500 B
    assert layers.attn_roofline(run) == pytest.approx(
        100 * 2 * 500e-9 / 110e-6)
    assert layers.idle_share(run) == pytest.approx(100 * (1 - 200 / 300))
    assert layers.step_hbm(run) == pytest.approx(
        100 * 2 * (14000 + 1000) * 1e-9 / 200e-6)


def test_unknown_device_kind_is_an_error():
    assert registry.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("TPU v9 imaginary")


def test_percentiles_are_of_all_samples():
    from harness.window import percentile
    assert percentile(np.arange(101), 95) == 95.0
