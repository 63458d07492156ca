"""The trace reduction, against a small trace recorded on a TPU v5e
(``record_fixture.py``: three runs of a tiny jitted program under
``bench/step`` annotations with 20 ms pauses between them) and against
hand-made intervals."""
import os

import pytest

from benchmark.lib import xplane

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixture_tpu.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce(FIXTURE)


def test_busy_is_the_union_of_op_intervals(trace):
    assert trace.chips == 1 and trace.op_events == 12
    # three runs of (copy-start 13 ns, copy-done 2-3 ns, 12.2-12.4 us of
    # matmul+tanh, 7.04 us of reduce), read off the events by hand
    assert trace.busy_s == pytest.approx(58.2e-6, rel=1e-3)
    assert trace.window_s == pytest.approx(45.462259e-3, rel=1e-6)
    assert trace.idle_share == pytest.approx(1 - 58.2e-6 / 45.462259e-3,
                                             rel=1e-6)


def test_modules_and_the_median_run(trace):
    assert list(trace.modules) == ["jit_step"]
    name, one_run_s, runs = trace.main_module()
    assert (name, runs) == ("jit_step", 3)
    assert one_run_s == pytest.approx(19.732e-6, rel=1e-6)


def test_operations_are_named_by_kind_dtype_and_shape(trace):
    ops = dict((k, v) for k, v in trace.top_ops(10))
    assert ops["convolution_tanh_fusion_bf16_512_1024_"] == pytest.approx(
        37.036e-6, rel=1e-3)
    assert "copy-start_bf16_1024_1024_" in ops
    assert trace.top_ops(1)[0][0] == "convolution_tanh_fusion_bf16_512_1024_"


def test_instructions_are_found_by_any_shape_in_their_text(trace):
    # the reduce fusion OUTPUTS a scalar but READS the bf16[512,1024]
    # activations: a search by operand shape finds it, one by output
    # shape does not
    by_text = trace.seconds_of_instructions(lambda t: "[512,1024]" in t)
    by_output = dict(trace.top_ops(10))[
        "convolution_tanh_fusion_bf16_512_1024_"]
    assert by_text == pytest.approx(58.15e-6, rel=2e-3)
    assert by_text > by_output


def test_idle_gaps_are_named_by_the_host_span(trace):
    gaps = dict(trace.top_gaps(10))
    # two pauses of 20 ms sit between the three runs
    assert list(gaps) == ["bench/pause"]
    assert gaps["bench/pause"] == pytest.approx(45.4e-3, rel=5e-3)


def test_op_label_parses_the_instruction_text():
    name = ("%multiply_reduce_fusion.12 = f32[320,12,512]{2,1,0:T(8,128)"
            "S(1)} fusion(f32[320,12,512]{2,1,0} %get-tuple-element.4)")
    assert xplane.op_label(name) == (
        "multiply_reduce_fusion_f32_320_12_512_", (320, 12, 512))
    tup = "%while = (s32[]{:T(128)}, pred[320]{0}, f32[320,12,512,64]{2,3})"
    assert xplane.op_label(tup) == ("while_s32__", ())
    assert xplane.op_label("%copy-done.3") == ("copy-done", None)


def test_union_merges_overlaps():
    total, merged = xplane.union_seconds([(0, 10), (5, 12), (20, 30),
                                          (30, 31), (22, 25)])
    assert total == 23 and merged == [[0, 12], [20, 31]]


def test_self_seconds_takes_nested_events_off_their_parent():
    # a while of 100 ns holding two body ops, then a plain op
    events = [(0, 100, "while"), (10, 40, "a"), (50, 90, "b"),
              (120, 130, "c")]
    got = {}
    for key, s in xplane.self_seconds(events):
        got[key] = got.get(key, 0.0) + s
    assert got == pytest.approx({"while": 30e-9, "a": 30e-9, "b": 40e-9,
                                 "c": 10e-9})


def test_host_activity_rule():
    bench = [(0, 100, "bench/submit")]
    host = [(90, 1000, "np.asarray(jax.Array)")]
    # the benchmark's span covers most of this gap
    assert xplane.host_activity(bench, host, 10, 110) == "bench/submit"
    # here jax's own event does
    assert xplane.host_activity(bench, host, 50, 950) == (
        "host:np.asarray(jax.Array)")
    # nothing covers a third of this one
    assert xplane.host_activity(bench, host, 2000, 3000) == "no_span"
    assert xplane.host_activity(bench, [], 90, 1000) == "no_span"
