"""The control: the reference in fp8 put in the program's place must come
out not correct by the harness's own rule, where the program passes (here at
small widths; on the chip at the cell's own size with ``run.py --control``)."""
import pytest

from chipbench.tests.conftest import small_mix, tiny_run


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_control_fails_where_program_passes(dense_cfg, seed):
    program = tiny_run(dense_cfg, small_mix("batch"), seed=seed, seconds=1.0)
    assert program["result"]["correct"], program["result"]["checks"]
    out = tiny_run(dense_cfg, small_mix("batch"), seed=seed, seconds=1.0,
                   control=True)
    res = out["result"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]
    assert out["run"]["program_logit_gap"] <= res["checks"]["logit_gap"]["limit"]
