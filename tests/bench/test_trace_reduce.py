"""The reduction from a profiler trace to per-chip intervals, on a trace
built by hand."""
import pathlib
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace  # noqa: E402


def ev(name, s, e):
    return NS(name=name, start_ns=float(s), end_ns=float(e))


def plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=evs)
                                for n, evs in lines.items()])


def test_union_subtract_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        (0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert trace.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert trace.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]


def test_self_times_of_nested_events():
    evs = [("while", 0, 10), ("fusion.1", 1, 4), ("fusion.2", 5, 7),
           ("copy", 12, 13)]
    assert trace.self_times(evs) == {"while": 5.0, "fusion.1": 3.0,
                                     "fusion.2": 2.0, "copy": 1.0}


@pytest.mark.parametrize("name,coll", [
    ("all-reduce.3", True), ("all-reduce-start.1", True),
    ("all-gather-done", True), ("reduce-scatter.7", True),
    ("collective-permute-start.2", True), ("all-to-all.1", True),
    ("fusion.12", False), ("convolution.3", False), ("copy-start", False),
])
def test_collective_names(name, coll):
    assert trace.is_collective(name) is coll


def _profile():
    """Two chips, a window [100, 200). Chip 0: compute 100-130, an
    all-reduce 125-160 (5 hidden under compute), compute 170-190.
    Chip 1: compute 100-150 only. Host: dispatch 100-120, loss_fetch
    120-195, data 195-200."""
    host = plane("/host:CPU", {"python": [
        ev("window", 100, 200), ev("dispatch", 100, 120),
        ev("loss_fetch", 120, 195), ev("data", 195, 200),
        ev("dispatch", 300, 310)]})
    d0 = plane("/device:TPU:0", {
        "XLA Ops": [ev("fusion.1", 90, 130), ev("all-reduce.1", 125, 160),
                    ev("fusion.2", 170, 190)],
        "XLA Modules": [ev("jit_step", 90, 190)]})
    d1 = plane("/device:TPU:1", {"XLA Ops": [ev("fusion.1", 100, 150)]})
    return NS(planes=[host, d0, d1])


def test_summarize_by_hand():
    s = trace.summarize(_profile())
    assert s.window == (100.0, 200.0) and s.steps == 1
    c0, c1 = s.chips
    assert c0.busy == 80.0 and c1.busy == 50.0       # 30 + 35 + 20 - 5
    assert c0.coll == 35.0 and c0.coll_exposed == 30.0
    assert c1.coll == 0.0 and c1.coll_exposed == 0.0
    # chip 0 idles 160-170 (loss_fetch) and 190-200 (5 loss_fetch, 5 data)
    assert c0.idle == {"loss_fetch": 15.0, "data": 5.0}
    assert c1.idle == {"loss_fetch": 45.0, "data": 5.0}
    # the all-reduce starts inside fusion.1 and outlasts it: it counts
    # against fusion.1's self time up to fusion.1's end
    assert c0.ops == {"fusion.1": 25.0, "all-reduce.1": 35.0,
                      "fusion.2": 20.0}
    b = trace.breakdown(s)
    assert [k for k, _ in b["idle_gaps"]] == ["loss_fetch", "data"]
    assert [v for _, v in b["idle_gaps"]] == pytest.approx([30e-9, 5e-9])
    assert [k for k, _ in b["device_ops"][:2]] == ["fusion.1",
                                                   "all-reduce.1"]
    assert [v for _, v in b["device_ops"][:2]] == pytest.approx(
        [37.5e-9, 17.5e-9])


def test_no_window_or_no_device_reads_nothing():
    p = _profile()
    assert trace.summarize(NS(planes=p.planes[1:])) is None
    assert trace.summarize(NS(planes=p.planes[:1])) is None


def test_metric_readers_by_hand():
    from benchmarks.chip import cells
    s = trace.summarize(_profile())
    ctx = NS(summary=s, steps=1, flops_per_step=1e4, chips=2,
             peak_flops=1e12)
    read = lambda n: cells.load_reader(n).read(ctx)
    assert read("idle_share") == pytest.approx(100 * (0.2 + 0.5) / 2)
    assert read("coll_ms") == pytest.approx(35 / 2 * 1e-6)
    assert read("coll_exposed_ms") == pytest.approx(30 / 2 * 1e-6)
    # 1e4 FLOPs in 100 ns on 2 chips of 1e12 FLOP/s: 5 %
    assert read("mfu") == pytest.approx(5.0)
    one = NS(summary=trace.Summary(s.window, [s.chips[1]], 1), steps=1,
             flops_per_step=1.0, chips=1, peak_flops=1.0)
    assert cells.load_reader("coll_ms").read(one) is None
    none = NS(summary=None, steps=3, flops_per_step=1.0, chips=1,
              peak_flops=1.0)
    for n in ("mfu", "idle_share", "coll_ms", "coll_exposed_ms"):
        assert cells.load_reader(n).read(none) is None
