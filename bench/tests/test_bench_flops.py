"""The benchmark's FLOP count against a count made by hand."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import flops, harness  # noqa: E402


def _cfg(name, where="configs"):
    return harness.dims(harness.load_json(
        harness.BENCH / where / f"{name}.json"))


def test_internlm2_step_flops_by_hand():
    a = _cfg("internlm2-1.8b")
    # per layer: q 2048x2048, k and v 2048x1024 each, o 2048x2048,
    # MLP 3 x 2048x8192; then the output head 2048 x 92544
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 8192
    weights = 24 * layer + 2048 * 92544
    assert flops.matmul_params(a) == weights == 1_699_479_552
    tokens = 2 * 4096
    # causal QK^T and PV: 2 products x 2*S*S*H*dh / 2, per sequence, layer
    attn = 24 * 2 * (2 * 2 * 4096 * 4096 * 16 * 128 // 2)
    want = 3 * (2 * weights * tokens + attn)
    assert flops.layer_flops_fwd(a, 2, 4096) == attn
    assert flops.train_step_flops(a, 2, 4096) == want
    assert abs(want - 9.3432e13) / want < 1e-3


def test_falcon_mamba_step_flops_by_hand():
    a = _cfg("falcon-mamba-7b.l8")
    # per layer: in_proj 4096 x 16384, x_proj 8192 x (256 + 32),
    # dt_proj 256 x 8192, out_proj 8192 x 4096; head 4096 x 65024
    layer = 4096 * 16384 + 8192 * 288 + 256 * 8192 + 8192 * 4096
    weights = 8 * layer + 4096 * 65024
    assert flops.matmul_params(a) == weights
    tokens = 2 * 512
    scan = 8 * tokens * (7 * 8192 * 16 + 2 * 4 * 8192)
    assert flops.train_step_flops(a, 2, 512) == 3 * (2 * weights * tokens + scan)
    assert flops.layer_flops_fwd(a, 2, 512) == scan


def test_tiny_hybrid_step_flops_by_hand():
    a = _cfg("tiny-hybrid", "tests/configs")
    # a period of two layers, twice: Mamba (in_proj 64 x 256, x_proj
    # 128 x (4 + 8), dt_proj 4 x 128, out_proj 128 x 64) and attention
    # (q 64x64, k and v 64x32 each, o 64x64), each with an MLP 3 x 64x128;
    # then the output head 64 x 256
    mamba = 64 * 256 + 128 * 12 + 4 * 128 + 128 * 64
    attention = 64 * 64 * 2 + 64 * 32 * 2
    mlp = 3 * 64 * 128
    weights = 2 * (mamba + attention + 2 * mlp) + 64 * 256
    assert flops.matmul_params(a) == weights == 192_512
    tokens = 2 * 128
    scan = 2 * tokens * (7 * 128 * 4 + 2 * 4 * 128)
    attn = 2 * 2 * (2 * 2 * 128 * 128 * 4 * 16 // 2)
    assert flops.layer_flops_fwd(a, 2, 128) == scan + attn
    assert flops.train_step_flops(a, 2, 128) == 3 * (2 * weights * tokens
                                                     + scan + attn)
