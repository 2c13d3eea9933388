"""The readers of the engine's split of the decode step, on a synthetic
record and on a traced CPU run of each mix."""
from types import SimpleNamespace

import pytest

from chipbench.harness import registry
from chipbench.tests.conftest import small_mix, tiny_run

SPLIT = [("host_sample_ms.batch", "decode.sample_s"),
         ("host_sample_ms.chat", "decode.sample_s"),
         ("step_prep_ms.batch", "decode.prep_s"),
         ("step_prep_ms.chat", "decode.prep_s")]


def _rec(**histograms):
    return SimpleNamespace(histograms=histograms)


@pytest.mark.parametrize("name,histogram", SPLIT)
def test_split_reader_means_in_ms(name, histogram):
    read = registry.metric_reader(name)
    got = read(_rec(**{histogram: {"count": 4, "sum": 0.1}}))
    assert got == pytest.approx(25.0)
    # a program without the histogram, or a window that decoded nothing
    assert read(_rec()) is None
    assert read(_rec(**{histogram: {"count": 0, "sum": 0.0}})) is None


@pytest.mark.parametrize("mix,cell", [("batch", "batch"), ("chat", "chat")])
def test_traced_run_reports_the_split(dense_cfg, mix, cell):
    out = tiny_run(dense_cfg, small_mix(mix), seconds=1.0, trace=True)
    metrics = out["result"]["metrics"]
    for quantity in ("host_sample_ms", "step_prep_ms"):
        assert metrics[f"{quantity}.{cell}"]["value"] > 0
        assert metrics[f"{quantity}.{cell}"]["unit"] == "ms"
    assert out["result"]["correct"]
