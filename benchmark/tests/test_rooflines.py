"""The roofline counts against numbers worked by hand for qwen3-4b."""
import pytest

from benchmark.harness.manifest import load_json
from benchmark.rooflines import qwen3_dense as counts

C = load_json("configs", "qwen3-4b")["model"]


def test_matmul_parameters_by_hand():
    # per layer: q 2560x4096, k and v 2560x1024 each, o 4096x2560, three of 2560x9728
    per_layer = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    assert per_layer == 100_925_440
    assert counts.matmul_params(C) == 36 * per_layer == 3_633_315_840
    assert counts.head_params(C) == 2560 * 151936 == 388_956_160


def test_decode_bytes_by_hand():
    assert counts.weight_bytes(C) == 2 * (3_633_315_840 + 388_956_160) == 8_044_544_000
    assert counts.kv_bytes_per_token(C) == 2 * 36 * 8 * 128 * 2 == 147_456   # 144 KiB a token
    assert counts.decode_step_bytes(C, 4000) == 8_044_544_000 + 4000 * 147_456
    # at 819 GB/s the weights alone are 9.82 ms a step
    assert counts.weight_bytes(C) / 819e9 == pytest.approx(9.82e-3, rel=1e-3)


def test_prefill_flops_by_hand():
    one = counts.prefill_flops(C, [384])
    matmul = 2 * 3_633_315_840 * 384
    attention = 36 * 2 * 2 * 32 * 128 * (384 * 385 / 2)
    head = 2 * 388_956_160
    assert one == pytest.approx(matmul + attention + head)
    assert one / 197e12 == pytest.approx(14.3e-3, rel=2e-2)   # ~14 ms at the bf16 peak
    half_cached = counts.prefill_flops(C, [384], cached=192)
    assert half_cached == pytest.approx(matmul / 2 + attention / 2 + head)
