"""Operation and byte counts from shapes, against hand counts, and the
table of peaks."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from benchmarks.chip import bench, flops  # noqa: E402
from benchmarks.chip.gen import Dims  # noqa: E402


def test_grouped_gemm_counts():
    # 8192 rows, D 2048, F 5632, 8 experts: gate+up 2*2*N*D*F, down 2*N*F*D
    n, D, F = 8192, 2048, 5632
    f = flops.grouped_gemm_fwd(n, 8, D, F)
    assert f["flops"] == 4 * n * D * F + 2 * n * F * D
    assert f["bytes"] == 8 * 3 * D * F * 2 + n * D * 2 + n * D * 2
    b = flops.grouped_gemm_bwd(n, 8, D, F)
    # dWg, dWu, dWd, dh, and dx through gate and up: six products of 2*N*D*F
    assert b["flops"] == 6 * 2 * n * D * F
    assert b["bytes"] == 8 * 3 * D * F * 2 + 8 * 3 * D * F * 4 + 3 * n * D * 2


def test_flash_attention_counts():
    # causal over S=4: 10 of 16 score entries; two products of 2*hd each
    f = flops.flash_fwd(1, 2, 1, 4, 8)
    assert f["flops"] == 2 * 10 * (2 * 8) * 2
    assert f["bytes"] == 2 * 4 * 8 * (2 + 2 + 1 + 1)
    assert flops.flash_bwd(1, 2, 1, 4, 8)["flops"] == 2 * f["flops"]


def dims(name):
    cell_cfg = bench.load_json(bench.HERE / "configs" / f"{name}.json")
    return Dims.from_config(cell_cfg, padded_vocab=0)


def test_train_flops_stablelm2_e8t2():
    d = dims("stablelm2-1.6b-e8t2")
    D, F, V, L, S = 2048, 5632, 25088, 2, 4096
    attn_proj = 4 * 2 * D * D  # q, k, v, o: 32 heads of 64 = D
    layer = attn_proj + 2 * D * 8 + 2 * 3 * 2 * D * F  # router, 2 of 8 experts
    scores = 4 * 32 * 64 * (S + 1) / 2
    want = 3 * (L * (layer + scores) + 2 * D * V)
    assert flops.train_flops_per_token(d, S) == pytest.approx(want)
    assert 1.3e9 < want < 1.6e9  # about 1.44 GFLOP per trained token


def test_train_flops_gqa_with_qkv_bias():
    # grouped-query attention counts K and V at the KV heads' width: the
    # widths of Qwen2.5-14B (40 query and 8 KV heads of 128), 2 layers
    d = Dims(d_model=5120, d_ff=13824, num_heads=40, num_kv_heads=8, head_dim=128,
             num_layers=2, vocab=38016, padded_vocab=38016, num_experts=8, top_k=2,
             norm="rmsnorm", norm_eps=1e-5, rope_theta=1e6, qkv_bias=True)
    D, F, V, L, S = 5120, 13824, 38016, 2, 1024
    proj = 2 * D * 40 * 128 + 2 * 2 * D * 8 * 128 + 2 * 40 * 128 * D
    layer = proj + 2 * D * 8 + 2 * 3 * 2 * D * F
    scores = 4 * 40 * 128 * (S + 1) / 2
    assert flops.train_flops_per_token(d, S) == pytest.approx(3 * (L * (layer + scores) + 2 * D * V))


def test_peaks_known_and_unknown():
    pk = flops.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v99")
    work = {"flops": 197e12, "bytes": 819e9 * 2}
    assert flops.least_seconds(work, pk) == pytest.approx(2.0)
