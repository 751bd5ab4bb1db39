"""The split of a traced unit over the program's own spans
(`bench/span_split.py`), on hand-built planes: which host events count
as program spans, their self time, and the device idle time each span
holds outside its nested spans."""
from __future__ import annotations

import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

import harness
import span_split as ss
import trace_reduce as tr


def ev(name, start_s, end_s):
    return NS(name=name, start_ns=start_s * 1e9, end_ns=end_s * 1e9)


def plane(name, lines):
    return NS(name=name, lines=[NS(name=k, events=v)
                                for k, v in lines.items()])


@pytest.mark.parametrize("name, program", [
    ("serve.epoch", True), ("d2h.wait", True),
    ("cluster.replay[sresume]", True), ("fleet.cluster.fused[clone]", True),
    ("bench.window", False), ("bench.serve.serve_trace", False),
    ("dot_general.1", False), ("TpuClient::LinearizeIntoImpl", False),
    ("PjitFunction(<lambda>)", False), ("Wait for usage holds", False),
    ("dce", False), ("$python.sleep", False)])
def test_program_spans_told_apart_by_name(name, program):
    assert bool(ss.PROGRAM_SPAN.match(name)) is program


def _planes():
    """Window [0, 20]. Chip 0 busy [0, 3], [5, 5.5], [11, 12], [14, 14.2],
    so idle [3, 5], [5.5, 11], [12, 14], [14.2, 20]."""
    main = [ev(tr.WINDOW, 0, 20), ev("bench.serve.serve_trace", 0.5, 19.5),
            ev("serve.trace", 1, 19),
            ev("serve.epoch", 2, 10), ev("serve.window", 3, 6),
            ev("d2h.wait", 4, 6),
            ev("serve.epoch", 11, 18), ev("serve.governor", 12, 17),
            ev("optimizer.solve_grid", 13, 15),
            ev("TpuClient::LinearizeIntoImpl", 13.5, 14.5),
            ev("serve.epoch", 21, 22)]
    other = [ev("workloads.synthesize", 5, 7)]
    host = plane("/host:CPU", {"python": main, "worker": other})
    dev = plane("/device:TPU:0", {
        "XLA Ops": [ev("a", 0, 3), ev("b", 5, 5.5), ev("c", 11, 12),
                    ev("d", 14, 14.2)],
        "XLA Modules": [ev("jit__window_core(1)", 0, 3),
                        ev("jit__window_core(1)", 5, 5.5),
                        ev("jit_solve_jobs(2)", 11, 12),
                        ev("jit__solve_grid_device(3)", 14, 14.2)]})
    busy_all = plane("/device:TPU:1", {"XLA Ops": [ev("x", 0, 20)]})
    return [host, busy_all, dev]


def test_self_and_idle_go_to_the_innermost_span():
    got = ss.program_spans(_planes())
    want = {
        # 18 s; its epochs take 8 + 7; idle 14.3 less the epochs' 6.5 + 5.8
        "serve.trace": (1, 18.0, 3.0, 2.0),
        # [2, 10] less its window [3, 6]; [11, 18] less its governor
        # [12, 17]; idle 6.5 - 2.5 and 5.8 - 4.8
        "serve.epoch": (2, 15.0, 5.0 + 2.0, 4.0 + 1.0),
        # idle [3, 5] + [5.5, 6], less its wait's [4, 5] + [5.5, 6]
        "serve.window": (1, 3.0, 1.0, 1.0),
        "d2h.wait": (1, 2.0, 2.0, 1.5),
        # idle [12, 14] + [14.2, 17] less the solve's [13, 14] + [14.2, 15]
        "serve.governor": (1, 5.0, 3.0, 3.0),
        # the runtime event nested in it is not a program span
        "optimizer.solve_grid": (1, 2.0, 2.0, 1.8),
        # another thread: its own nesting, the same idle gaps
        "workloads.synthesize": (1, 2.0, 2.0, 1.5),
    }
    assert set(got) == set(want)
    for name, (count, t, t_self, t_idle) in want.items():
        assert got[name] == {"count": count, "time_s": pytest.approx(t),
                             "self_s": pytest.approx(t_self),
                             "idle_s": pytest.approx(t_idle)}, name


def test_program_spans_need_window_and_device():
    dev = plane("/device:TPU:0", {"XLA Ops": []})
    with pytest.raises(ValueError, match="annotation"):
        ss.program_spans([plane("/host:CPU", {"python": []}), dev])
    with pytest.raises(ValueError, match="device plane"):
        ss.program_spans([plane("/host:CPU",
                                {"python": [ev(tr.WINDOW, 0, 1)]})])


def test_epoch_split_per_epoch_numbers():
    planes = _planes()
    reduced = tr.reduce(planes, chips=1)
    split = ss.epoch_split(reduced, ss.program_spans(planes))
    assert split == pytest.approx({
        "serve_epoch_host_ms": 7_500.0, "epoch_solve_host_ms": None,
        "serve_window_host_ms": 3_000.0, "combine_host_ms": 0.0,
        "governor_host_ms": 2_500.0, "d2h_wait_ms": 1_000.0,
        "d2h_per_epoch": 0.5, "programs_per_epoch": 2.0,
        # epochs: 15 s, 7 s of it their own
        "epoch_children_share": 8.0 / 15.0,
        # window idle 20 - 4.7 = 15.3; 2.0 of it left to serve.trace
        "unit_idle_share": 2.0 / 15.3})
    assert ss.epoch_split(reduced, {}) == {}


def test_main_entry_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "span_split.py"),
         "--workload", "storm-online-sresume", "--seed", "1"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
