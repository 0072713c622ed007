"""The trace reduction on hand-built device and host intervals."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace as tr  # noqa: E402


def dev(ops, modules=()):
    return tr.Device("/device:TPU:0", ops=sorted(ops),
                     modules=sorted(modules))


WINDOW = [(0.0, 100.0, "bench.window")]


def test_busy_is_the_union_of_operations_inside_the_window():
    d = dev([(10, 30, "fusion.1"), (20, 40, "fusion.2"), (90, 120, "x")])
    t = tr.from_parts([d], WINDOW)
    assert tr.busy_ns(d, t.window) == 30 + 10
    assert t.window_s == pytest.approx(100e-9)
    assert tr.mean_busy_s(t) == pytest.approx(40e-9)


def test_exposed_collective_is_what_no_other_operation_overlaps():
    d = dev([(0, 50, "all-reduce-start.3"), (10, 20, "fusion.1"),
             (40, 70, "convolution.2"), (80, 90, "all-gather.1")])
    # all-reduce 0-50 minus compute 10-20 and 40-50: 30; all-gather 10
    assert tr.exposed_ns(d, (0, 100)) == 30 + 10


def test_exposed_is_zero_when_compute_covers_the_collective():
    d = dev([(10, 20, "reduce-scatter.1"), (0, 15, "fusion.9"),
             (15, 30, "fusion.10")])
    assert tr.exposed_ns(d, (0, 100)) == 0


def test_kernel_time_sums_matching_operations():
    d = dev([(0, 5, "paged_decode_attn.1"), (10, 12, "fusion"),
             (20, 26, "paged_decode_attn.2")])
    assert tr.matching_ns(d.ops, (0, 100),
                          lambda n: "paged_decode" in n) == 11
    assert tr.count_matching(d.ops, (0, 100), tr.is_collective) == 0


def test_module_time_by_program_name():
    d = dev([], modules=[(0, 10, "jit_prefill(1)"), (10, 13, "jit_scatter"),
                         (20, 60, "jit_chunk_fn(2)")])
    got = tr.matching_ns(d.modules, (0, 100),
                         lambda n: n.startswith(("jit_prefill",
                                                 "jit_scatter")))
    assert got == 13


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    d = dev([(0, 20, "a"), (50, 100, "b")])
    spans = WINDOW + [(15, 45, "bench.chunk"), (25, 40, "bench.submit")]
    t = tr.from_parts([d], spans)
    assert tr.idle_gaps(d, t.window) == [(20, 50)]
    assert tr.idle_by_label(t) == [["submit", pytest.approx(30e-9)]]
    assert tr.label_of(18, t.spans) == "chunk"
    assert tr.label_of(99, t.spans) == "untraced"


def test_top_ops_average_over_devices():
    d0 = dev([(0, 10, "f"), (10, 15, "g")])
    d1 = tr.Device("/device:TPU:1", ops=[(0, 30, "f")], modules=[])
    t = tr.from_parts([d0, d1], WINDOW)
    assert t.devices[0].name == "/device:TPU:0"
    assert tr.top_ops(t) == [["f", pytest.approx(20e-9)],
                             ["g", pytest.approx(2.5e-9)]]


def test_window_defaults_to_the_extent_of_the_operations():
    t = tr.from_parts([dev([(5, 9, "a"), (12, 30, "b")])], [])
    assert t.window == (5, 30)


def test_subtract_of_merged_intervals():
    assert tr.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22)]) == [
        (0, 2), (4, 8), (22, 30)]


def test_op_names_are_short_and_keep_a_kernel_name():
    assert tr.op_name("%fusion.12 = bf16[2]{0} fusion(%a), kind=kLoop") == \
        "fusion.12"
    text = ('%custom-call.3 = bf16[16,32,128]{2,1,0} custom-call(%q), '
            'custom_call_target="tpu_custom_call", '
            'backend_config={"name": "_paged_decode_kernel"}')
    assert tr.op_name(text) == "_paged_decode_kernel:custom-call.3"
    assert tr.module_name("jit_prefill(1234)") == "jit_prefill"


def test_containers_are_not_leaves():
    ops = [(0, 100, "while.4"), (0, 10, "fusion.1"), (20, 30, "fusion.2"),
           (120, 130, "fusion.3")]
    assert [n for _, _, n in tr.leaves(ops)] == ["fusion.1", "fusion.2",
                                                 "fusion.3"]
    d = dev(ops + [(40, 60, "all-reduce.1")])
    assert tr.exposed_ns(d, (0, 200)) == 20


def _prefill_reading(modules, admitted, groups):
    from bench import harness
    t = tr.from_parts([dev([], modules=modules)],
                      [(0.0, 100e6, "bench.window")])
    x = harness.LayerInputs(trace=t, peaks={}, chips=1, config={},
                            counts={"admitted": admitted,
                                    "prefill_groups": groups})
    return harness.metric_reader("prefill_ms.serve").read(x)


PREFILLS = [(0, 4e6, "jit__unknown"), (4e6, 5e6, "jit_scatter"),
            (5e6, 6e6, "jit_scatter"), (30e6, 34e6, "jit__unknown"),
            (34e6, 35e6, "jit_scatter"), (40e6, 90e6, "jit_chunk_fn")]


@pytest.mark.parametrize("modules, admitted, groups, expected", [
    (PREFILLS, 3, 2, 11 / 3),                          # counts agree
    (PREFILLS, 3, 3, None),                            # a prefill missing
    (PREFILLS + [(95e6, 96e6, "jit__unknown")], 3, 2, None),   # one extra
    ([iv for iv in PREFILLS if iv[2] != "jit__unknown"], 3, 2, None),
    ([(a, b, "jit_prefill" if n == "jit__unknown" else n)
      for a, b, n in PREFILLS], 3, 2, 11 / 3),         # once it is named
])
def test_prefill_reader_reads_only_when_its_programs_are_counted(
        modules, admitted, groups, expected):
    got = _prefill_reading(sorted(modules), admitted, groups)
    assert got == (pytest.approx(expected) if expected else None)


# -- the program's spans ----------------------------------------------------

PROGRAM = [tr.Span(30, 60, "serve.step", {}),
           tr.Span(15, 45, "serve.tables", {"width": 256, "live": 12}),
           tr.Span(32, 40, "serve.scatter", {"rid": 7, "slot": 3}),
           tr.Span(70, 80, "train.round", {"round": 2})]


def test_from_parts_keeps_program_spans_and_their_args():
    t = tr.from_parts([dev([(0, 20, "a")])], WINDOW, PROGRAM)
    assert [s.name for s in t.program_spans] == [
        "serve.tables", "serve.step", "serve.scatter", "train.round"]
    by = {s.name: s for s in t.program_spans}
    assert by["serve.tables"].args == {"width": 256, "live": 12}
    assert (by["serve.scatter"].start, by["serve.scatter"].end) == (32, 40)
    assert by["serve.scatter"].args["rid"] == 7
    assert tr.from_parts([dev([(0, 20, "a")])], WINDOW).program_spans == []


@pytest.mark.parametrize("reduce", [
    tr.idle_by_label, tr.outline, tr.top_ops, tr.mean_busy_s,
    lambda t: t.spans, lambda t: t.window])
def test_program_spans_leave_labels_and_outline_as_they_were(reduce):
    d = dev([(0, 20, "a"), (50, 100, "b")])
    spans = WINDOW + [(15, 45, "bench.chunk"), (25, 40, "bench.submit")]
    assert reduce(tr.from_parts([d], spans, PROGRAM)) == \
        reduce(tr.from_parts([d], spans))


def test_load_keeps_the_program_spans_of_a_real_trace(tmp_path):
    """Under the profiler on the CPU: spans opened through the program's
    ``repro.obs.span`` reach ``program_spans`` with their args; the
    harness's spans alone reach ``spans``."""
    import glob
    import jax
    import jax.numpy as jnp
    from repro.obs import span
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with span("serve.step"):
                with span("serve.tables", width=16, live=5):
                    jnp.ones(4).block_until_ready()
            with span("train.round", round=1):
                pass
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    t = tr.load(path)
    assert [n for _, _, n in t.spans] == ["bench.window"]
    by = {s.name: s for s in t.program_spans}
    assert sorted(by) == ["serve.step", "serve.tables", "train.round"]
    assert by["serve.tables"].args == {"width": 16, "live": 5}
    assert by["train.round"].args == {"round": 1}
    step, tables = by["serve.step"], by["serve.tables"]
    assert step.start <= tables.start and tables.end <= step.end


# The serving readers on one set of inputs, against what they read before
# the trace kept program spans and the counts came from the reference.
READ_BEFORE = {"mfu.serve": 7.5226465836385845,
               "paged_decode_roofline": 24.727432319618366,
               "prefill_ms.serve": 3.6666666666666665,
               "device_idle.serve": 88.85}


def _serve_inputs(program_spans=()):
    import json
    from bench import harness, peaks
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(repo, "bench", "configs", "yi-6b-l4.json")) as fh:
        cfg = json.load(fh)
    ops = [(0, 2e6, "fusion.1"),
           (2e6, 2.5e6, "_paged_decode_kernel:custom-call.3"),
           (3e6, 9e6, "convolution_bitcast_fusion.2"),
           (9e6, 9.4e6, "_paged_decode_kernel:custom-call.4"),
           (40e6, 41e6, "fusion.7"),
           (60e6, 61.25e6, "_paged_decode_kernel:custom-call.3")]
    modules = [(0, 4e6, "jit_prefill"), (4e6, 5e6, "jit_scatter"),
               (5e6, 6e6, "jit_scatter"), (30e6, 34e6, "jit_prefill"),
               (34e6, 35e6, "jit_scatter"), (40e6, 90e6, "jit_chunk_fn")]
    t = tr.from_parts([dev(ops, modules)],
                      [(0.0, 100e6, "bench.window"),
                       (10e6, 30e6, "bench.chunk")], program_spans)
    steps = [[1019, 1500, 40], [1020, 1501, 41, 7], [3000] * 16]
    return harness.LayerInputs(
        trace=t, peaks=peaks.peaks_for("TPU v5 lite"), chips=1, config=cfg,
        counts={"decode_steps": steps, "admitted": 3, "prefill_groups": 2,
                "weight_bytes": 2, "kv_bytes": 2})


@pytest.mark.parametrize("metric", sorted(READ_BEFORE))
@pytest.mark.parametrize("with_program_spans", [False, True])
def test_serving_readers_read_what_they_read_before(metric,
                                                    with_program_spans):
    from bench import harness
    spans = [tr.Span(1e6, 2e6, "serve.tables", {"width": 256, "live": 9})] \
        if with_program_spans else ()
    got = harness.metric_reader(metric).read(_serve_inputs(spans))
    assert got == READ_BEFORE[metric]
