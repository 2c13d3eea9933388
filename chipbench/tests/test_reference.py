"""The reference against ServeEngine at the ``.reduced()`` widths, through
a whole harness run (shape warm-up, warm traffic, window, check)."""
import pytest

from chipbench.tests.conftest import small_mix, tiny_run


@pytest.mark.parametrize("mix", ["batch", "chat"])
def test_dense_engine_matches_reference(dense_cfg, mix):
    out = tiny_run(dense_cfg, small_mix(mix), seconds=1.5)
    res, run = out["result"], out["run"]
    assert run["window_compiles"] == 0
    assert res["correct"], res["checks"]
    assert run["checked_requests"] >= 1
    assert "tokens_per_s" in res["metrics"] or "itl_p95_ms" in res["metrics"]
