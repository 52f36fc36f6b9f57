"""The benchmark's work functions against FLOPs and bytes counted by hand."""
import bench_smoke  # noqa: F401  (puts the repo root on the path)
from bench.model import ModelSpec
from bench import work

S = ModelSpec(name="t", d_model=4, n_heads=2, n_kv_heads=1, head_dim=2,
              d_ff=6, vocab=10, n_layers=3, qkv_bias=False, tied=True,
              rope_theta=1e4, rope_fraction=1.0, norm_eps=1e-6, n_slots=1,
              max_context=16, kv_pool_tokens=16)
PEAK = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}


def test_dense_and_head_flops():
    # per layer: q 4x4, k 4x2, v 4x2, o 4x4, mlp 3 x 4x6 = 16+8+8+16+72
    assert work.dense_flops_per_token(S) == 2 * 120 * 3
    assert work.head_flops(S) == 2 * 4 * 10


def test_attention_counts():
    # 3 queries at positions 5, 6, 7 see 6 + 7 + 8 = 21 keys
    assert work.attn_flops(S, 3, 5) == 4 * 2 * 2 * 21 * 3
    # K and V of 8 positions (1 kv head x 2) + q and out of 3 (2 heads x 2)
    assert work.attn_bytes(S, 3, 5) == 2 * (2 * 1 * 2 * 8 + 2 * 2 * 2 * 3) * 3


def test_step_flops_and_roofline():
    spans = [(3, 5, True), (1, 9, False)]
    want = (work.dense_flops_per_token(S) * 4 + work.attn_flops(S, 3, 5)
            + work.attn_flops(S, 1, 9) + work.head_flops(S))
    assert work.step_flops(S, spans) == want
    assert work.roofline_seconds(200.0, 10.0, PEAK) == 2.0
    assert work.roofline_seconds(100.0, 40.0, PEAK) == 4.0


def test_peaks_table():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    try:
        work.peaks("cpu")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
