"""The trace reducer on a small trace recorded on one v5e chip (PR 23):
three rounds of a flash forward, a 4096^2 bf16 product and a flash
backward inside ``bench.step``, each followed by a 20 ms sleep inside
``bench.idle``, with a second thread under ``bench.other_thread``."""

import os

import pytest

from benchmark.trace import reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return reduce.Trace.from_file(DATA)


def test_planes_and_spans(trace):
    assert list(trace.device_ops) == [0]
    assert len(trace.device_ops[0]) == 54
    names = {n for _, _, n in trace.host_spans}
    assert names == {"bench.step", "bench.idle", "bench.other_thread"}


def test_busy_idle_and_attribution(trace):
    r = trace.reduce(window_span="bench.absent")
    assert r["window_s"] == pytest.approx(0.05885, rel=1e-3)
    assert r["busy_s"] == pytest.approx(0.015335, rel=1e-3)
    assert r["busy_s"] <= sum(r["ops"].values()) + 1e-9
    idle = r["idle_gaps"]
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
    # the sleeps are where the device waited
    assert max(idle, key=idle.get) == "bench.idle"
    assert idle["bench.idle"] > 0.035


def test_window_span_clips(trace):
    a, b = trace.window("bench.step")
    inside = trace.reduce(window_span="bench.step")
    assert inside["window_s"] == pytest.approx(b - a)
    assert inside["busy_s"] <= trace.reduce("bench.absent")["busy_s"]


def test_labels_and_shapes(trace):
    r = trace.reduce(window_span="bench.absent")
    top = reduce.top(r["ops"], 3)
    assert top[0][0] == "transpose_jvp_flash_bwd_dkv__.1_bf16_64_2048_128_"
    text = next(t for _, t in r["events"] if "flash_fwd.1" in t)
    assert reduce.op_name(text) == "flash_fwd.1"
    shapes = reduce.shapes_in(text)
    assert shapes[0] == ("bf16", (64, 2048, 128))
    assert ("f32", (64, 2048, 1)) in shapes


def test_attribute_innermost_and_no_span():
    spans = [(0.0, 10.0, "outer"), (2.0, 4.0, "inner")]
    got = reduce._attribute(1.0, 5.0, spans)
    assert got == pytest.approx({"outer": 2.0, "inner": 2.0})
    assert reduce._attribute(11.0, 12.0, spans) == {"_no_span_": 1.0}


def test_a_gap_is_named_by_the_workers_region_under_the_benchmarks_span():
    """The load generator's ``bench.wait_reply`` slices and the decode
    worker's ``perf.decode.*`` regions lie in one trace: the worker's
    names the gap, also where the generator's slice began later."""
    spans = [(0.0, 4.0, "perf.decode.step"),
             (1.0, 3.0, "perf.decode.step.dispatch"),
             (2.0, 6.0, "bench.wait_reply"),
             (4.5, 5.0, "perf.decode.sched")]
    got = reduce._attribute(0.5, 6.0, spans, reduce.SPAN_PREFIXES)
    assert got == pytest.approx({
        "perf.decode.step": 1.5, "perf.decode.step.dispatch": 2.0,
        "bench.wait_reply": 1.5, "perf.decode.sched": 0.5})


def test_both_layers_regions_are_kept_from_a_file(tmp_path):
    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.wait_reply"):
        with jax.profiler.TraceAnnotation("perf.decode.step.dispatch"):
            jnp.ones((8, 8)).sum().block_until_ready()
        with jax.profiler.TraceAnnotation("perf.phase.other"):
            pass
    jax.profiler.stop_trace()
    trace = reduce.Trace.from_file(reduce.find_xplane(str(tmp_path)))
    assert {n for _, _, n in trace.host_spans} == {
        "bench.wait_reply", "perf.decode.step.dispatch"}
    only = reduce.Trace.from_file(reduce.find_xplane(str(tmp_path)),
                                  span_prefixes=("bench.",))
    assert {n for _, _, n in only.host_spans} == {"bench.wait_reply"}
