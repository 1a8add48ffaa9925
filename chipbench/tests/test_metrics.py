"""The operation and byte counts of each per-layer metric, and each
reader on hand-made reductions, against values worked out by hand."""

import importlib.util
import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.metrics import costs

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_mpdata_step_bytes():
    assert costs.mpdata_step_bytes(4, 6) == 2 * 4 * 6 * 4 == 192


def test_decode_attn_cost():
    # two live slots of 10 and 20 positions, 4 heads, 2 KV heads, size 8
    flops, byts = costs.decode_attn_cost([10, 20], 4, 2, 8)
    assert byts == 2 * 30 * 2 * 8 * 2 + 2 * 2 * 4 * 8 * 2 == 2176
    assert flops == 4 * 30 * 4 * 8 == 3840


def test_decoder_token_flops():
    m = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
             d_ff=16, vocab_size=10)
    # per layer: q,k,v,o 8*4*(2*2+2*1) = 192, MLP 3*8*16 = 384 -> 2*576
    # attention at position 3: 4 * 4 keys * 2 heads * 4 = 128
    assert costs.dense_layer_params(8, 2, 1, 4, 16) == 576
    assert costs.decoder_token_flops(m, 3, False) == 2 * (1152 + 128)
    assert costs.decoder_token_flops(m, 3, True) == 2 * (1152 + 128) + 160


def _red(ops, spans=None, modules=()):
    return tr.Reduced(window=(0, 10**9), spans=spans or {},
                      devices=[tr.Device("/device:TPU:0", ops,
                                         list(modules))])


def test_decode_attn_roofline_reader():
    m = dict(n_layers=4, n_heads=32, n_kv_heads=4, head_dim=128)
    red = _red([("decode_attention.3", 0, 10**6)])       # 1 ms of kernel
    info = {"decode_kernel": "decode_attention", "model": m,
            "decode_steps": [[100, 200]]}
    byts = 4 * (2 * 300 * 4 * 128 * 2 + 2 * 2 * 32 * 128 * 2)
    assert reader("decode_attn_roofline")(red, info, PEAKS) == \
        pytest.approx(100 * byts / 819e9 / 1e-3)
    assert reader("decode_attn_roofline")(_red([]), info, PEAKS) is None


def test_step_mfu_readers():
    m = dict(n_layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
             d_ff=16, vocab_size=10)
    mods = [("jit__decode_fn(1)", 0, 1000), ("jit__prefill_fn(2)", 2000,
                                                4000)]
    red = _red([("fusion", 0, 4000)], modules=mods)
    info = {"model": m, "decode_program": "_decode_fn",
            "prefill_program": "_prefill_fn",
            "decode_steps": [[4]],                 # one slot at position 3
            "prefill_steps": [[(0, 2, True)]]}     # rows 0, 1; prompt done
    dec = costs.decoder_token_flops(m, 3, True)
    assert reader("decode.step_mfu")(red, info, PEAKS) == pytest.approx(
        100 * dec / (1e-6 * 197e12))
    pre = (costs.decoder_token_flops(m, 0, False)
           + costs.decoder_token_flops(m, 1, False) + 2 * 8 * 10)
    assert reader("prefill.step_mfu")(red, info, PEAKS) == pytest.approx(
        100 * pre / (2e-6 * 197e12))


def test_mpdata_hbm_share_reader():
    red = _red([("fusion", 0, 10**9)])                  # busy the whole 1 s
    info = {"traced_steps": 100, "grid": 1024, "mesh": [1, 1],
            "bytes_per_cell": 4}
    want = 100 * (2 * 1024 * 1024 * 4 / 819e9) / 0.01
    assert reader("mpdata.step_hbm_share")(red, info, PEAKS) == \
        pytest.approx(want)


def test_host_gap_and_halo_readers():
    ops = [("fusion.1", 15, 25), ("fusion.2", 55, 60)]
    red = tr.Reduced(window=(0, 100), devices=[
        tr.Device("/device:TPU:0", ops, [])],
        spans={"engine.step": [(10, 30), (50, 70)]})
    assert reader("serve.host_gap_share")(red, {}, PEAKS) == \
        pytest.approx(25.0)
    assert reader("halo.exposed_share")(red, {}, PEAKS) is None
    red.devices[0].ops.append(("collective-permute-done", 80, 90))
    assert reader("halo.exposed_share")(red, {}, PEAKS) == \
        pytest.approx(10.0)
