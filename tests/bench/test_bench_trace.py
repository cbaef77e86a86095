"""The trace reduction, on a recorded trace and on one made here.

``data/v5e_minitron_serve_events.json.gz`` holds the events of three
iterations of a profiler trace recorded on one TPU v5e in the
``minitron-4b-8l.batch-decode`` cell (see ``test_bench_serve_spans.py``):
three decode steps of 8 layers and one prefill chunk, with the Pallas
kernels named as the program names them. ``data/gqa_readings.json`` holds
what the reduction read on it before kernels were found by name.
"""
import gzip
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import serve_spans  # noqa: E402
import trace_reduce  # noqa: E402

DECODE, PREFILL = "paged_decode_attention", "paged_prefill_attention"


@pytest.fixture(scope="module")
def events():
    with gzip.open(HERE / "data" / "v5e_minitron_serve_events.json.gz",
                   "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def before():
    with open(HERE / "data" / "gqa_readings.json") as f:
        return json.load(f)["minitron_fixture"]


def test_kernels_are_told_apart_by_their_names(events):
    ops = events["devices"][0]["ops"]
    names = [trace_reduce.kernel_name(n) for n, _, _ in ops]
    kernels = [k for k in names if k is not None]
    # three decode steps of one kernel call per layer of the 8, and one
    # prefill chunk step
    assert kernels.count(DECODE) == 24 and kernels.count(PREFILL) == 8
    assert set(kernels) == {DECODE, PREFILL}
    assert trace_reduce.kernel_name("%fusion.1 = f32[8] fusion(...)") is None
    # a kernel the reduction has never seen keeps its own name
    assert trace_reduce.kernel_name(
        "%grouped_matmul.3 = bf16[64,512]{1,0} custom-call(s32[40]{0} %a),"
        ' custom_call_target="tpu_custom_call"') == "grouped_matmul"


def test_reduction_of_the_recorded_trace(events):
    r = trace_reduce.reduce(events)
    dev = events["devices"][0]
    w0, w1 = [(s, e) for n, s, e in events["host"] if n == "bench.window"][0]
    assert r.window_s == pytest.approx((w1 - w0) / 1e9)
    assert 0 < r.busy_s <= r.window_s
    # busy time is at least every whole module inside the window, which do
    # not overlap on one core
    inside = [(s, e) for _, s, e in dev["modules"] if s >= w0 and e <= w1]
    assert r.busy_s >= sum(e - s for s, e in inside) / 1e9 - 1e-12
    assert len(r.decode_steps_s) == 3
    assert all(0.04 < t < 0.07 for t in r.decode_steps_s)
    assert r.prefill_programs_s > 0
    assert r.kernel_calls == {DECODE: 24, PREFILL: 8}
    assert r.kernel_s[DECODE] < sum(r.decode_steps_s)
    # the labels of gaps come from the program's serve spans
    assert r.gaps and all(g.startswith("serve.") for g, _ in r.gaps)
    idle = r.window_s - r.busy_s
    assert sum(s for _, s in r.gaps) <= idle + 1e-9
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(v, float) for _, v in b["device_ops"])
    # self times never exceed the busy time they are part of
    assert sum(r.op_self_s.values()) <= r.busy_s + 1e-9


def test_reduction_reads_what_it_read_by_operand_shape(events, before):
    """Kernels by name read exactly what the first operand's rank read:
    decode (rank 2) is the decode kernel, prefill (rank 1) the prefill."""
    r = trace_reduce.reduce(events)
    got = before["trace"]
    assert r.kernel_s == {DECODE: got["kernel_s"]["decode"],
                          PREFILL: got["kernel_s"]["prefill"]}
    assert r.kernel_calls == {DECODE: got["kernel_calls"]["decode"],
                              PREFILL: got["kernel_calls"]["prefill"]}
    assert r.decode_steps_s == got["decode_steps_s"]
    assert r.prefill_programs_s == got["prefill_programs_s"]
    assert (r.window_s, r.busy_s) == (got["window_s"], got["busy_s"])
    assert r.breakdown()["device_ops"] == before["device_ops"]


def test_breakdown_labels_gaps_by_serve_span(events, before):
    r = trace_reduce.reduce(events)
    # the split the readers read is made with the reduction, unchanged
    assert {p: r.split.idle_ms(p) for p in serve_spans.PARTS} == \
        before["idle_ms"]
    assert r.split.host_step_ms() == before["host_step_ms"]
    gaps = r.breakdown()["idle_gaps"]
    assert gaps == before["label_gaps"]
    assert gaps[0][0] == "serve.decode_inputs"
    # a device clock placed 1.4 ms early: the gaps are found on the host's
    # clock, as the split finds them
    early = dict(events, devices=[
        {**d, **{k: [(n, a - 1.4e6, b - 1.4e6) for n, a, b in d[k]]
                 for k in ("modules", "ops")}} for d in events["devices"]])
    r = trace_reduce.reduce(early)
    assert r.split.shift_s[0] > 1e-3
    assert r.gaps == serve_spans.label_gaps(early, r.split.idle)
    w = trace_reduce.window(early, early["devices"])
    on_device = trace_reduce.idle(trace_reduce.busy(early["devices"][0], *w),
                                  *w)
    assert r.gaps != serve_spans.label_gaps(early, on_device)
    # without serve spans, the harness's spans label the gaps
    ev = dict(events, serve=[])
    r = trace_reduce.reduce(ev)
    assert r.split is None
    assert {g for g, _ in r.gaps} <= {"Engine.step", "Engine.submit",
                                      "outside"}


def test_no_device_events_reduce_to_nothing():
    assert trace_reduce.reduce({"devices": [], "host": []}) is None


def test_extract_reads_a_trace_made_here(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("Engine.step"):
        with jax.profiler.TraceAnnotation("serve.iteration"):
            jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_trace(tmp_path)
    assert path is not None
    ev = trace_reduce.extract(path)
    assert [n for n, _, _ in ev["host"]] == ["Engine.step"]
    assert [n for n, _, _ in ev["serve"]] == ["serve.iteration"]
    # the CPU has no device plane with XLA modules
    assert ev["devices"] == [] and trace_reduce.reduce(ev) is None
