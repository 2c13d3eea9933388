"""The work counts against hand counts for one layer."""
from chipbench.tests.conftest import small_config
from chipbench.work import dense


def test_dense_one_layer():
    cfg = small_config("qwen25_3b", num_hidden_layers=1)
    d, f, v = 128, 256, 512
    q, kv = 4 * 32, 2 * 32
    assert dense.matmul_params(cfg) == d * q + 2 * d * kv + q * d + 3 * d * f + d * v
    # one token at context 10: 2 FLOPs per weight + q.k and p.v per head
    assert dense.decode_token_flops(cfg, 10) == 2 * dense.matmul_params(cfg) + 4 * 4 * 32 * 10
    flops, nbytes = dense.paged_attn_call(cfg, [10, 6])
    assert flops == 4 * 4 * 32 * 16
    # K and V rows of 2 heads x 32 in bf16 for 16 positions, plus 2 q and 2 o rows
    assert nbytes == 2 * 2 * 32 * 2 * 16 + 2 * 4 * 32 * 2 * 2
