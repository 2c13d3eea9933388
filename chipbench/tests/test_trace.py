"""The trace reduction on a small recorded trace: busy-interval union,
kernel-event sums, program counts and labelled idle gaps."""
import json

import pytest

from chipbench.harness import registry
from chipbench.harness import trace as tr


@pytest.fixture
def events():
    with open(registry.BENCH / "tests" / "data" / "trace_small.json") as f:
        rows = json.load(f)["events"]
    return [tr.Event(*r) for r in rows]


def test_union_merges_overlaps_and_clips():
    busy, gaps = tr.union([(0, 10), (5, 20), (30, 40)], 2, 35)
    assert busy == 18 + 5
    assert gaps == [(20, 10)]


def test_reduce(events):
    r = tr.reduce(events)
    # window 1000..11000; ops cover 1000..2900 (clipped, nested ops merged)
    # and 9000..11000
    assert r.window_ns == 10000
    assert r.busy_ns == 1900 + 2000
    assert r.op_ns("paged_attention") == 1000
    # the while op's own time is what its nested ops leave: 1000..2900
    # clipped, less fusion.1 (1000..1500) and the kernel (1800..2800)
    assert r.ops["%fusion.1"] == 500
    assert r.ops["%while.1"] == 1900 - 500 - 1000
    assert r.module("_decode_impl") == (2, 5000)
    # one idle gap, 2900..9000, cut where the host spans end
    assert r.gaps == [("chipbench.step", 2100), ("chipbench.harvest", 3000),
                      ("no host span", 1000)]


def test_breakdown(events):
    b = tr.breakdown(tr.reduce(events))
    assert b["device_ops"][0] == ["%fusion.2", pytest.approx(2000e-9)]
    assert b["idle_gaps"][0] == ["chipbench.harvest", pytest.approx(3000e-9)]


def test_no_window_or_device_reads_nothing(events):
    assert tr.reduce([e for e in events if e.name != tr.WINDOW_SPAN]) is None
    assert tr.reduce([e for e in events if not e.plane.startswith("/device")]) is None
