"""The trace reduction on a small trace recorded on the chip
(``data/<cell>_small.xplane.pb.gz``, made by record_trace.py and
gzipped): the cell at faulty_run.py's small size, a few steps under the
profiler. The reduction's readings are checked against a count made
another way, on a 100 ns grid, and against the values they had when the
trace was committed."""
import gzip
import pathlib
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from benchmarks.chip import cells, trace  # noqa: E402

GRID_NS = 100.0
#: the readings when each trace was committed
RECORDED = {
    "qwen1.5-4b_small.xplane.pb.gz": {
        "steps": 2, "window_ns": 5486869.0,
        "idle_share": 91.65828453349259, "coll_ms": None,
        "coll_exposed_ms": None,
        "device_ops": [["fusion.1 f32[1024]", 2.6465e-05],
                       ["fusion.2 bf16[1024,256]", 2.2651e-05],
                       ["bitcast_dynamic-update-slice_fusion.16 "
                        "bf16[2,2,512,640]", 2e-05]],
        "idle_gaps": [["loss_fetch", 0.002573259],
                      ["dispatch", 0.0015295690000000002],
                      ["data", 0.0008948210000000001]]},
}


@pytest.fixture(scope="module", params=sorted(
    p.name for p in (HERE / "data").glob("*.xplane.pb.gz")))
def recorded(request):
    from jax.profiler import ProfileData
    raw = gzip.decompress((HERE / "data" / request.param).read_bytes())
    return request.param, ProfileData.from_serialized_xspace(raw)


def _grid_count(profile):
    """Per chip, on a 100 ns grid over the window: busy, collective and
    exposed collective time, and idle time by host span."""
    spans = trace.host_spans(profile)
    lo = min(s for s, _ in spans["window"])
    hi = max(e for _, e in spans["window"])
    n = int(np.ceil((hi - lo) / GRID_NS))

    def mask(intervals):
        """Grid points (cell centres) that some interval covers."""
        d = np.zeros(n + 1, np.int64)
        for s, e in intervals:
            i = int(np.clip(np.ceil((s - lo) / GRID_NS - 0.5), 0, n))
            j = int(np.clip(np.ceil((e - lo) / GRID_NS - 0.5), 0, n))
            if j > i:
                d[i] += 1
                d[j] -= 1
        return np.cumsum(d[:n]) > 0

    out = {}
    for dev, (ops, flights) in trace.device_ops(profile).items():
        busy = mask((s, e) for _, s, e in ops)
        coll = mask([(s, e) for name, s, e in ops + flights
                     if trace.is_collective(name)])
        other = mask((s, e) for name, s, e in ops
                     if not trace.is_collective(name))
        idle = {k: float(((~busy) & mask(spans[k])).sum()) * GRID_NS
                for k in trace.HOST_SPANS}
        out[dev] = {"busy": busy.sum() * GRID_NS,
                    "coll": coll.sum() * GRID_NS,
                    "exposed": (coll & ~other).sum() * GRID_NS,
                    "idle": idle}
    return (lo, hi), out


def test_reduction_agrees_with_a_grid_count(recorded):
    _, profile = recorded
    s = trace.summarize(profile)
    window, grid = _grid_count(profile)
    assert s.window == window
    tol = 2e-3 * s.window_ns
    assert sorted(grid) == [c.device for c in s.chips]
    for c in s.chips:
        g = grid[c.device]
        assert c.busy == pytest.approx(g["busy"], abs=tol)
        assert c.coll == pytest.approx(g["coll"], abs=tol)
        assert c.coll_exposed == pytest.approx(g["exposed"], abs=tol)
        for k in trace.HOST_SPANS:
            assert c.idle.get(k, 0.0) == pytest.approx(g["idle"][k], abs=tol)


def test_readings_as_committed(recorded):
    name, profile = recorded
    want = RECORDED[name]
    s = trace.summarize(profile)
    assert s.steps == want["steps"]
    assert s.window_ns == pytest.approx(want["window_ns"], rel=1e-12)
    ctx = NS(summary=s, steps=s.steps, flops_per_step=1.0,
             chips=len(s.chips), peak_flops=1.0)
    for metric in ("idle_share", "coll_ms", "coll_exposed_ms"):
        got = cells.load_reader(metric).read(ctx)
        if want[metric] is None:
            assert got is None
        else:
            assert got == pytest.approx(want[metric], rel=1e-9)
    b = trace.breakdown(s, 3)
    assert [k for k, _ in b["device_ops"]] == [k for k, _ in
                                               want["device_ops"]]
    assert [v for _, v in b["device_ops"]] == pytest.approx(
        [v for _, v in want["device_ops"]], rel=1e-9)
    assert dict(b["idle_gaps"]) == pytest.approx(dict(want["idle_gaps"]),
                                                 rel=1e-9)
