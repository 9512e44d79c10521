"""The trace reduction, on hand-made events and on a small recorded trace
(two ISG batches on one TPU v5e: ``data/isg_two_batches.json``, the plain
events ``trace.load`` made of the profiler's file)."""
import json
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"


def events(ops, spans, modules=()):
    return {"devices": {"/device:TPU:0": {"ops": [list(o) for o in ops],
                                          "modules": [list(m) for m in modules]}},
            "host": {"/host:CPU/main": [list(s) for s in spans]}}


def test_merge_and_complement():
    merged = trace.merge([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 0, 25)
    assert merged == [(0, 3), (5, 12), (20, 25)]
    assert trace.complement(merged, 0, 25) == [(3, 5), (12, 20)]
    assert trace.complement([], 0, 10) == [(0, 10)]


def test_gaps_go_to_the_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.ingress", 0, 10),
             ("bench.dispatch_host", 10, 60), ("bench.classify", 15, 40),
             ("bench.egress_wait", 60, 95)]
    ops = [("jit_dispatch/fusion.1", 20, 30), ("jit_dispatch/fusion.2", 50, 70)]
    red = trace.reduce(events(ops, spans))
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(30e-9)
    assert red.idle_share == pytest.approx(0.7)
    # idle: [0,20) [30,50) [70,100)
    assert red.gap_s == pytest.approx({
        "bench.ingress": 10e-9, "bench.dispatch_host": 15e-9,
        "bench.classify": 15e-9, "bench.egress_wait": 25e-9,
        "host.other": 5e-9})
    assert sum(red.gap_s.values()) + red.busy_s == pytest.approx(red.window_s)


def test_intervals_are_clipped_to_the_window():
    spans = [("bench.window", 100, 200)]
    ops = [("p/a", 50, 150), ("p/a", 190, 250), ("p/b", 10, 20)]
    red = trace.reduce(events(ops, spans, [("p(1)", 40, 260)]))
    assert red.busy_s == pytest.approx(60e-9)
    assert red.op_s == pytest.approx({"p/a": 60e-9})
    assert red.op_n == {"p/a": 2.0}
    assert red.module_n == {"p(1)": 1}


def test_kernel_calls_are_found_by_name_in_any_program():
    spans = [("bench.window", 0, 100)]
    ops = [("jit_dispatch/dfa_regex.1", 0, 10), ("jit_dispatch/dfa_regex.1", 20, 30),
           ("jit_other/dfa_regex", 40, 45), ("jit_dispatch/dfa_regex_2.1", 50, 60),
           ("jit_dispatch/fusion.3", 60, 70)]
    red = trace.reduce(events(ops, spans))
    secs, calls = red.op_time("dfa_regex")
    assert (secs, calls) == (pytest.approx(25e-9), 3.0)


def test_ops_are_named_by_the_program_that_holds_them():
    modules = [["jit_dispatch(81)", 0, 100], ["jit__lookup_jnp(9)", 200, 250]]
    ops = [["%fusion.12 = u8[8] fusion(u8[8] %p)", 10, 20],
           ["%fusion.12 = s32[4] fusion(s32[4] %q)", 210, 220],
           ["%copy.1 = u8[8] copy(u8[8] %x)", 150, 160]]
    assert [o[0] for o in trace.qualify(ops, modules)] == [
        "jit_dispatch/fusion.12", "jit__lookup_jnp/fusion.12", "?/copy.1"]


def test_a_trace_needs_its_window_and_the_device():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(events([("p/a", 0, 1)], [("bench.ingress", 0, 1)]))
    with pytest.raises(ValueError, match="no device"):
        trace.reduce({"devices": {}, "host": {"t": [["bench.window", 0, 9]]}})


def test_recorded_trace_of_two_isg_batches():
    red = trace.reduce(json.loads((DATA / "isg_two_batches.json").read_text()))
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.134362817)
    assert red.busy_s == pytest.approx(0.065717337)
    dispatch = [n for n in red.module_n if trace.program_name(n) == "jit_dispatch"]
    assert [red.module_n[n] for n in dispatch] == [2]
    assert red.op_time("dfa_regex") == (pytest.approx(0.030855282), 2.0)
    assert red.op_time("arx_cipher") == (pytest.approx(0.000891271), 2.0)
    assert red.op_time("keyed_hash") == (pytest.approx(0.000340384), 2.0)
    assert set(red.gap_s) <= {"bench.ingress", "bench.dispatch_host",
                              "bench.egress_wait", "host.other"}
    assert sum(red.gap_s.values()) + red.busy_s == pytest.approx(red.window_s)
    bd = trace.breakdown(red)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0] == "jit_dispatch/dfa_regex.1"
    assert [v for _, v in bd["device_ops"]] == sorted(
        (v for _, v in bd["device_ops"]), reverse=True)


def test_load_keeps_each_threads_benchmark_spans(tmp_path):
    """Two threads of one process can share a line name; neither's spans
    may be lost."""
    import threading

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones(8)
    f(x).block_until_ready()

    def other():
        with jax.profiler.TraceAnnotation("bench.waiter"):
            f(x).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.ingress"):
            f(x).block_until_ready()
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=60)
    jax.profiler.stop_trace()
    assert not th.is_alive()
    ev = trace.load(str(tmp_path))
    by_thread = sorted(sorted(s[0] for s in spans)
                       for spans in ev["host"].values())
    assert by_thread == [["bench.ingress", "bench.window"], ["bench.waiter"]]
