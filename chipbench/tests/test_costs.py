"""Operation and byte counts against hand counts."""
import json

from chipbench.costs import bracket, dense_lm
from conftest import ROOT

QWEN = json.loads((ROOT / "chipbench/configs/qwen2.5-3b.json").read_text())


def test_qwen25_3b_parameters():
    m = QWEN["model"]
    # per layer: q 2048x2048, k and v 2048x256 each, o 2048x2048, three
    # MLP matrices 2048x11008; the tied 151936x2048 table
    layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    assert dense_lm.matmul_params(m) == 36 * layer + 151936 * 2048
    extra = 36 * (2048 + 2 * 256 + 2 * 2048) + 2048     # biases, norms
    assert dense_lm.all_params(m) == 36 * layer + 151936 * 2048 + extra
    assert dense_lm.all_params(m) == 3_085_938_688      # published 3.09 B
    assert dense_lm.kv_bytes_per_token(m) == 36 * 2 * 2 * 128 * 2


def test_forward_flops():
    m = QWEN["model"]
    n = dense_lm.matmul_params(m)
    assert dense_lm.forward_flops(m, 0, 1) == 2 * n
    # one token attending to 1000 positions: 4 * layers * d_model each
    assert dense_lm.forward_flops(m, 1000, 1) == 2 * n + 4 * 36 * 2048 * 1000


def test_bracket_counts():
    assert bracket.bracket_ops(10, 3, 2, 1) == 10 * (12 + 18 + 4)
    assert bracket.bracket_bytes(10, 3, 2, 1, 5) == 4 * (20 + 18 + 200)
