"""Jamba2-3B's operation and byte counts against hand counts and against
the parameter tree the program builds."""
import json

import jax

from chipbench import hybrid_lm
from chipbench.costs import hybrid_lm as costs
from conftest import ROOT

#: the configuration file, which holds the published keys at its top level
M = json.loads((ROOT / "chipbench/configs/jamba2-3b.json").read_text())


def test_jamba2_3b_parameters():
    # 26 Mamba layers: in_proj 2560x10240, x_proj 5120x(160+32), dt_proj
    # 160x5120, out_proj 5120x2560; 2 attention layers: q and o
    # 2560x2560, k and v 2560x128; 28 MLPs of 3 x 2560x8192; the tied
    # 65536x2560 table
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    attn = 2 * 2560 * 2560 + 2 * 2560 * 128
    mm = 26 * mamba + 2 * attn + 28 * 3 * 2560 * 8192 + 65536 * 2560
    assert costs.matmul_params(M) == mm
    # conv taps and bias, dt bias, A_log, D, the inner norms; two norms a
    # layer and the final norm
    extra = 26 * (4 * 5120 + 3 * 5120 + 5120 * 16 + 160 + 32) \
        + 28 * 2 * 2560 + 2560
    assert costs.all_params(M) == mm + extra == 3_029_337_472   # 3.03 B


def test_all_params_is_the_program_tree():
    model = hybrid_lm.make_model(M)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(tree)) == \
        costs.all_params(M)


def test_state_and_kv_bytes():
    # two attention layers, one KV head of 128, bf16 keys and values
    assert costs.kv_bytes_per_token(M) == 2 * 2 * 128 * 2 == 1024
    # 26 layers of a bf16 conv tail (3 x 5120) and a f32 (5120, 16) state
    assert costs.state_bytes_per_slot(M) == \
        26 * (3 * 5120 * 2 + 5120 * 16 * 4) == 9_318_400


def test_forward_flops_and_chunk_least_time():
    n = costs.matmul_params(M)
    scan = costs.scan_flops_per_token(M)
    assert scan == 26 * (2 * 4 * 5120 + 6 * 5120 * 16)
    assert costs.forward_flops(M, 0, 1) == 2 * n + scan
    # one token attending to 1000 positions in each of the 2 attention
    # layers: 4 * 20 heads * 128 each
    assert costs.forward_flops(M, 1000, 1) == \
        2 * n + scan + 4 * 2 * 2560 * 1000
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    full = costs.chunk_least_s(M, 0, 256, peaks)
    assert full == costs.forward_flops(M, 256 * 257 / 2, 256) / 197e12
    one = costs.chunk_least_s(M, 512, 1, peaks)     # bytes-bound
    assert one == (2 * costs.all_params(M) + 513 * 1024
                   + 2 * 9_318_400) / 819e9
