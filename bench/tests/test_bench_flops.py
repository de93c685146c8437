"""FLOP and byte counts against hand counts at chatglm3-6b's widths."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops, spec  # noqa: E402

GLM = spec.load_json(spec.BENCH_DIR / "configs" / "chatglm3-6b.json")["model"]
YI = spec.load_json(spec.BENCH_DIR / "configs" / "yi-9b-tp4.json")["model"]


def test_gemm_decode_projections():
    q = flops.gemm(64, 4096, 4096, bias=True)            # wq, 64 slots
    assert q["flops"] == 2 * 64 * 4096 * 4096 == 2_147_483_648
    assert q["bytes"] == 2 * (64 * 4096 + 4096 * 4096 + 64 * 4096 + 4096)
    g = flops.gemm(64, 4096, 13696)                      # w_gate
    assert g["flops"] == 7_180_648_448
    assert g["bytes"] == 2 * (262_144 + 56_098_816 + 876_544) == 114_475_008


def test_attn_decode_paged_live_contexts():
    c = flops.attn_decode_paged([100, 200], 32, 2, 128)
    assert c["flops"] == 4 * 32 * 128 * 300 == 4_915_200
    # K and V of 300 positions over 2 KV heads, plus q and out of 2 slots
    assert c["bytes"] == 2 * 2 * 128 * 300 * 2 + 2 * 2 * 32 * 128 * 2
    assert c["bytes"] == 339_968


def test_kv_bytes_per_token():
    assert flops.kv_bytes_per_token(GLM) == 28_672
    # 2 (K, V) x 48 layers x 4 KV heads x 128 x 2 bytes; 24,576 a chip
    assert flops.kv_bytes_per_token(YI) == 98_304


def test_decode_step_weights_and_flops():
    per_layer = 4096 * 4608 + 4608 + 4096 * 4096 + 3 * 4096 * 13696 + 8192
    assert per_layer == 203_960_832
    params = 28 * per_layer + 4096 + 4096 * 65024
    assert flops.weight_params(GLM) == params == 5_977_245_696
    norms = 28 * 8192 + 4096
    empty = flops.decode_step(GLM, [])
    assert empty["bytes"] == (params - norms) * 2 + 4 * norms
    assert empty["flops"] == 0
    one = flops.decode_step(GLM, [1000])
    proj = 28 * (4096 * 4608 + 4096 * 4096 + 3 * 4096 * 13696) \
        + 4096 * 65024
    attn = 28 * 4 * 32 * 128 * 1000
    assert one["flops"] == 2 * proj + attn == 11_953_766_400 + attn
    assert one["bytes"] == empty["bytes"] + 28_672 * 1000 + 4096 * 2


def test_roofline_takes_the_larger_bound():
    pk = spec.load_json(spec.BENCH_DIR / "peaks.json")["devices"][
        "TPU v5 lite"]
    g = flops.gemm(64, 4096, 13696)
    t = flops.roofline_s(g, pk["bf16_flops"], pk["hbm_bytes_per_s"])
    assert t == pytest.approx(114_475_008 / 819e9)          # memory bound
    big = flops.gemm(8192, 8192, 8192)
    assert flops.roofline_s(big, pk["bf16_flops"], pk["hbm_bytes_per_s"]) \
        == pytest.approx(2 * 8192 ** 3 / 197e12)             # compute bound


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
