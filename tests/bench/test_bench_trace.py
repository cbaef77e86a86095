"""The trace reduction, on a recorded trace and on one made here.

``data/v5e_qwen2_trace_events.json.gz`` holds the events of 53 ms of a
profiler trace recorded on one TPU v5e while qwen2-0.5b (published widths)
served chunked prefills and a decode step through the paged engine: the
device's ``XLA Modules`` and ``XLA Ops`` events and the host's
``Engine.step`` spans, as ``trace_reduce.extract`` writes them.
"""
import gzip
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def events():
    with gzip.open(HERE / "data" / "v5e_qwen2_trace_events.json.gz",
                   "rt") as f:
        return json.load(f)


def test_kernels_are_told_apart_by_their_page_table(events):
    ops = events["devices"][0]["ops"]
    kinds = [trace_reduce.kernel_kind(n) for n, _, _ in ops]
    decode = [n for (n, _, _), k in zip(ops, kinds) if k == "decode"]
    prefill = [n for (n, _, _), k in zip(ops, kinds) if k == "prefill"]
    # one decode step: one kernel call per layer of the 24
    assert len(decode) == 24
    assert all("custom-call(s32[8,64]" in n for n in decode)
    assert prefill and all("custom-call(s32[64]" in n for n in prefill)
    assert trace_reduce.kernel_kind("%fusion.1 = f32[8] fusion(...)") is None


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
    assert len(r.decode_steps_s) == 1
    assert 0.005 < r.decode_steps_s[0] < 0.02
    assert r.prefill_programs_s > 0
    assert r.kernel_calls["decode"] == 24
    assert r.kernel_s["decode"] < r.decode_steps_s[0]
    # the labels of gaps come from the host spans
    assert r.gaps and {g for g, _ in r.gaps} <= {"Engine.step", "no span"}
    idle = r.window_s - r.busy_s
    assert sum(s for _, s in r.gaps) <= idle + 1e-9
    b = r.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert all(isinstance(v, float) for _, v in b["device_ops"])
    # self times never exceed the busy time they are part of
    assert sum(r.op_self_s.values()) <= r.busy_s + 1e-9


def test_no_device_events_reduce_to_nothing():
    assert trace_reduce.reduce({"devices": [], "host": []}) is None


def test_extract_reads_a_trace_made_here(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("Engine.step"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_trace(tmp_path)
    assert path is not None
    ev = trace_reduce.extract(path)
    assert [n for n, _, _ in ev["host"]] == ["Engine.step"]
    # the CPU has no device plane with XLA modules
    assert ev["devices"] == [] and trace_reduce.reduce(ev) is None
