"""Operation and byte counts against arithmetic done by hand at small
shapes, and the peaks table."""

import os

import pytest

from chipbench import cell
from chipbench.costs import dense_gqa, mamba2

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DENSE = dict(num_hidden_layers=2, hidden_size=8, intermediate_size=16,
             num_attention_heads=4, num_key_value_heads=2, head_dim=2,
             vocab_size=10, sliding_window=3, torch_dtype="bfloat16")
SSM = dict(n_layer=2, d_model=4, expand=2, headdim=2, d_state=3, d_conv=4,
           vocab_size=10, torch_dtype="bfloat16")


def test_dense_counts_by_hand():
    # per layer: q 8*4*2=64, k,v 2*8*2*2=64, o 64, mlp 3*8*16=384 -> 576
    # two layers 1152, head 8*10=80 -> 1232 matmul weights
    assert dense_gqa.matmul_weights(DENSE) == 1232
    # norms: 2 per layer and the final one, 8 wide -> 40; bf16
    assert dense_gqa.weight_bytes(DENSE) == 2 * (1232 + 40)
    # k and v, 2 layers, 2 kv heads of 2, bf16: 2*2*2*2*2
    assert dense_gqa.kv_bytes_per_token(DENSE) == 32
    # a token seeing 2 keys: 2*1232, and 4*H*hd = 32 per key and layer,
    # 2 layers, 2 keys -> 2464 + 128
    assert dense_gqa.token_flops(DENSE, 2) == 2592
    # the window caps the keys at 3
    assert dense_gqa.token_flops(DENSE, 9) == 2464 + 64 * 3


def test_dense_tick_by_hand():
    # slot 0: 5 resident, decodes 1 (sees min(6,3)=3 keys), reads 3 resident;
    # slot 1: fresh, feeds 2 (sees 1 and 2 keys), reads 0; slot 2 idle
    flops, nbytes = dense_gqa.tick(DENSE, [5, 0, 7], [1, 2, 0])
    per_key = 4 * 4 * 2 * 2
    assert flops == 3 * 2464 + per_key * (3 + 1 + 2)
    assert nbytes == 2 * (1232 + 40) + (3 + 3) * 32


def test_dense_train_flops():
    # seq 2: mean over contexts 1 and 2 of token_flops, times 3
    assert dense_gqa.train_flops_per_token(DENSE, 2) == 3 * (2464 + 64 * 1.5)


def test_mamba2_counts_by_hand():
    # di 8, N 3, P 2, H 4; per layer 4*(16+6+4) + 8*4 = 136; 2 layers 272;
    # tied head 4*10=40 -> 312
    assert mamba2.matmul_weights(SSM) == 312
    # state f32 4*3*2*4 = 96 B, conv window 3*(8+6) bf16 = 84 B; 2 layers
    assert mamba2.state_bytes_per_slot(SSM) == 2 * (96 + 84)
    # 2*312 + 2 layers * (5*4*3*2 + 2*4*14)
    assert mamba2.token_flops(SSM) == 624 + 2 * (120 + 112)
    flops, nbytes = mamba2.tick(SSM, [0, 9, 4], [16, 1, 0])
    assert flops == 17 * (624 + 464)
    assert nbytes == mamba2.weight_bytes(SSM) + 2 * 2 * 360
    # weights: bf16 (312 matmul + 4 final norm) + per layer bf16 (4 + 8 +
    # 5*14) and three f32 vectors of 4
    assert mamba2.weight_bytes(SSM) == 2 * 316 + 2 * (2 * 82 + 48)


def test_peaks_table():
    p = cell.peaks(REPO, "TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        cell.peaks(REPO, "cpu")
