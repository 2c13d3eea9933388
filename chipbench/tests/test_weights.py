"""One layer drawn alone equals its slice of the stacked draw, so the
reference sees the program's weights; seeds past 32 bits work."""
import jax
import numpy as np

from chipbench.harness import weights
from chipbench.reference import dense
from chipbench.tests.conftest import small_config


def test_layer_draw_matches_stacked_slice():
    cfg = small_config("qwen25_3b")
    bits = weights.seed_bits(2**33 + 17)
    stacked = jax.jit(lambda b: weights.stacked_weights(dense, cfg, b))(bits)
    for layer in range(dense.n_layers(cfg)):
        one = jax.jit(lambda b, l: weights.layer_weights(dense, cfg, b, l))(bits, layer)
        for k, v in one.items():
            assert v.dtype == stacked[k].dtype
            np.testing.assert_array_equal(np.asarray(v, np.float32),
                                          np.asarray(stacked[k][layer], np.float32))


def test_seeds_differ():
    cfg = small_config("qwen25_3b")
    a = weights.global_weights(dense, cfg, weights.seed_bits(1))["embed"]
    b = weights.global_weights(dense, cfg, weights.seed_bits(2))["embed"]
    assert not np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
