"""The reduction from a profiler trace to device intervals, module times
and host-annotated gaps, on hand-built planes and on a small trace
recorded on one TPU v5e chip (`bench/testdata/small.xplane.pb`)."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

import trace_reduce as tr

TESTDATA = tr.Path(__file__).resolve().parents[1] / "testdata"


def ev(name, start_s, end_s):
    return NS(name=name, start_ns=start_s * 1e9, end_ns=end_s * 1e9)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def test_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert tr.union_length(iv) == pytest.approx(3.0)
    assert tr.gaps(iv, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 5.0)]
    assert tr.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_self_time_subtracts_nested_ops():
    ops = [("while", 0.0, 10.0), ("cond", 1.0, 9.0), ("body", 2.0, 5.0),
           ("body", 6.0, 8.0), ("after", 10.0, 11.0)]
    got = {}
    for n, t in tr.self_times(ops):
        got[n] = got.get(n, 0.0) + t
    assert got == pytest.approx({"while": 2.0, "cond": 3.0, "body": 5.0,
                                 "after": 1.0})


def test_module_names_drop_jit_prefix_and_id():
    assert tr.module_name("jit__cluster_core(1234)") == "_cluster_core"
    assert tr.module_name("jit_solve_jobs(7)") == "solve_jobs"


def test_reduce_hand_built_planes():
    host = plane("/host:CPU", python=[
        ev(tr.WINDOW, 1.0, 11.0), ev("bench.unit", 1.0, 6.0),
        ev("bench.unit", 6.0, 11.0), ev("host.sleep", 4.0, 6.5)])
    dev = plane(
        "/device:TPU:0",
        XLA_Ops=[ev("fusion.1", 0.0, 2.0), ev("fusion.1", 2.5, 4.0),
                 ev("sort.2", 3.0, 3.5), ev("fusion.1", 7.0, 10.0)],
        XLA_Modules=[ev("jit__cluster_core(3)", 0.0, 4.0),
                     ev("jit__cluster_core(3)", 7.0, 10.0)])
    other = plane("/device:TPU:1", XLA_Ops=[ev("x", 1.0, 11.0)])
    r = tr.reduce([host, dev, other], chips=1)
    assert r["window_s"] == pytest.approx(10.0)
    # ops inside [1, 11]: [1, 2] + [2.5, 4] + [7, 10] = 1 + 1.5 + 3
    assert r["busy_s"] == pytest.approx(5.5)
    assert r["modules"]["_cluster_core"]["time_s"] == pytest.approx(6.0)
    assert r["modules"]["_cluster_core"]["count"] == 2
    # self time: sort.2 [3, 3.5] runs nested in fusion.1 [2.5, 4]
    assert r["kernels"] == pytest.approx({"fusion.1": 5.0, "sort.2": 0.5})
    assert r["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(5.0)]
    gaps = r["breakdown"]["idle_gaps"]
    # idle: [2, 2.5], [4, 7], [10, 11]; the longest is named by the
    # innermost host annotation around its middle (5.5: host.sleep)
    assert gaps[0] == ["host.sleep", pytest.approx(3.0)]
    assert [g[1] for g in gaps] == pytest.approx([3.0, 1.0, 0.5])
    r2 = tr.reduce([host, dev, other], chips=2)
    assert r2["busy_s"] == pytest.approx((5.5 + 10.0) / 2)


def test_reduce_needs_window_and_device():
    dev = plane("/device:TPU:0", XLA_Ops=[])
    with pytest.raises(ValueError, match="annotation"):
        tr.reduce([plane("/host:CPU", python=[]), dev], 1)
    with pytest.raises(ValueError, match="device plane"):
        tr.reduce([plane("/host:CPU", python=[ev(tr.WINDOW, 0, 1)])], 1)


def test_reduce_recorded_v5e_trace():
    """`small.xplane.pb`: one TPU v5e chip, a window of three rounds of
    two small jitted programs (`f`: matmul + sin + sum; `g`: exp +
    cumsum), each round followed by a 2 ms host sleep under the
    annotation `bench.test.sleep`.

    Checked by hand from the raw events: the window annotation lasts
    13,122,870 ns; the six module executions last 9,312 + 7,436 + 9,311 +
    7,256 + 9,545 + 7,428 = 50,288 ns; the op intervals inside them
    cover 50,210 ns (the first `f`, say: copy-start 13 ns, then
    copy-done and fusion back to back for 1,723 + 7,570 ns, so 9,306 of
    its 9,312 ns); the longest idle stretch falls in a sleep.
    """
    import jax
    data = jax.profiler.ProfileData.from_file(
        str(TESTDATA / "small.xplane.pb"))
    r = tr.reduce(data.planes, chips=1)
    assert r["window_s"] == pytest.approx(13_122_870e-9)
    assert r["busy_s"] == pytest.approx(50_210e-9)
    assert r["modules"] == {"_lambda": {"time_s": pytest.approx(50_288e-9),
                                        "count": 6}}
    assert r["kernels"]["fusion"] == pytest.approx(
        3 * (7_570 + 403) * 1e-9, rel=1e-3)
    name, longest = r["breakdown"]["idle_gaps"][0]
    assert name == "bench.test.sleep" and 2e-3 < longest < 4e-3
    assert len(r["breakdown"]["device_ops"]) == 10
