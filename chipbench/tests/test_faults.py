"""A run whose timed path is broken underneath comes out not correct: a
token altered where the engine produces it, at prefill and at decode."""
import numpy as np
import pytest

import repro.serve.engine as engine_mod
from chipbench.tests.conftest import small_mix, tiny_run


class _ShiftedArgmax:
    """numpy, with argmax one token off."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def argmax(a, *args, **kw):
        return (np.argmax(a, *args, **kw) + 1) % np.shape(a)[-1]


@pytest.mark.parametrize("where", ["prefill", "decode"])
def test_altered_token_is_caught(dense_cfg, monkeypatch, where):
    if where == "prefill":
        real = engine_mod.prefill_logits_token
        monkeypatch.setattr(engine_mod, "prefill_logits_token",
                            lambda logits: (real(logits) + 1) % logits.shape[-1])
    else:
        monkeypatch.setattr(engine_mod, "np", _ShiftedArgmax())
    out = tiny_run(dense_cfg, small_mix("batch"), seconds=1.0)
    res = out["result"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]
