"""The program's ``serve.*`` spans in a profiler trace, and the split of the
device's idle time by them (``bench/serve_spans.py``).

``data/v5e_minitron_serve_events.json.gz`` holds the events of three
iterations of a profiler trace recorded on one TPU v5e in the
``minitron-4b-8l.batch-decode`` cell: the device's ``XLA Modules`` and
``XLA Ops`` events, the harness's spans and the program's ``serve.*`` spans,
as ``trace_reduce.extract`` writes them, with ``bench.window`` cut to the
three iterations.
"""
import gzip
import json
import pathlib
import re

import jax
import numpy as np
import pytest

import bench_smoke
from bench_smoke import ROOT, harness

import serve_spans  # noqa: E402  (bench/, on the path through bench_smoke)
import trace_reduce  # noqa: E402
from repro import configs
from repro.models import blocks, transformer
from repro.serve.cache import CacheConfig
from repro.serve.engine import Engine, EngineConfig, Request

HERE = pathlib.Path(__file__).resolve().parent
FIXTURE = HERE / "data" / "v5e_minitron_serve_events.json.gz"
READERS = ("idle_sched_ms", "idle_dispatch_ms", "idle_fetch_ms",
           "idle_outside_ms")


def emitted_names():
    """Every serve span name the program can emit, read from its source."""
    src = "\n".join(p.read_text() for p in
                    (ROOT / "src" / "repro" / "serve").glob("*.py"))
    names = {f"serve.{n}" for n in re.findall(r'\.span\(\s*"(\w+)"', src)}
    names |= {f"serve.dispatch.{k}"
              for k in re.findall(r'\.dispatch\(\s*"(\w+)"', src)}
    if re.search(r"tracer\.iteration\(", src):
        names.add("serve.iteration")
    return names


def test_the_grouping_table_names_what_the_program_emits():
    assert set(serve_spans.GROUP) == emitted_names()
    assert set(serve_spans.GROUP.values()) == {"sched", "dispatch", "fetch"}


def test_an_untraced_engine_puts_its_spans_on_the_profiler(tmp_path):
    cfg = configs.get_smoke_config("qwen2-0.5b")
    params, _ = blocks.split_params(
        transformer.init_model(jax.random.PRNGKey(0), cfg))
    eng = Engine(cfg, params, config=EngineConfig(
        n_slots=2, max_seq=32, chunked=True, token_budget=8,
        cache=CacheConfig(paged=True, page_tokens=8)))
    rng = np.random.default_rng(0)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for i in range(3):
        eng.submit(Request(seq_id=i, max_new=3,
                           prompt=rng.integers(0, cfg.vocab, 6 + 4 * i)
                           .astype(np.int32)))
    done = eng.run(max_steps=50)
    jax.profiler.stop_trace()
    assert len(done) == 3
    # the tracer itself is off: it recorded nothing
    assert not eng.tracer.enabled and len(eng.tracer.events) == 0
    ev = trace_reduce.extract(trace_reduce.find_trace(tmp_path))
    names = {n for n, _, _ in ev["serve"]}
    assert names >= {"serve.iteration", "serve.submit", "serve.schedule",
                     "serve.commit", "serve.decode_inputs",
                     "serve.dispatch.decode", "serve.dispatch.prefill_chunk",
                     "serve.fetch_tokens"}
    assert names <= set(serve_spans.GROUP)
    # every phase of a step nests inside its iteration
    iters = [(s, e) for n, s, e in ev["serve"] if n == "serve.iteration"]
    assert len(iters) == eng.scheduler._iterations
    for n, s, e in ev["serve"]:
        if n not in ("serve.iteration", "serve.submit"):
            assert any(a <= s and e <= b for a, b in iters), n


def _events():
    """One device, window [0, 120] ns; idle [20, 50], [70, 90], [110, 120]."""
    mods = [("jit_decode_step(1)", 0.0, 20.0), ("jit_decode_step(1)", 50.0,
                                                70.0),
            ("jit_decode_step(1)", 90.0, 110.0)]
    serve = [("serve.iteration", 10.0, 60.0),
             ("serve.fetch_tokens", 10.0, 25.0),
             ("serve.commit", 25.0, 30.0),
             ("serve.decode_inputs", 30.0, 40.0),
             ("serve.cow_copy", 32.0, 35.0),
             ("serve.dispatch.decode", 40.0, 45.0),
             ("serve.iteration", 75.0, 95.0),
             ("serve.fetch_tokens", 75.0, 85.0)]
    host = [("bench.window", 0.0, 120.0), ("Engine.step", 9.0, 61.0),
            ("Engine.step", 74.0, 96.0), ("Engine.submit", 105.0, 120.0)]
    return {"devices": [{"name": "/device:TPU:0", "modules": mods,
                         "ops": []}],
            "host": host, "serve": serve}


def test_idle_is_charged_to_the_innermost_span():
    s = serve_spans.split(_events())
    ns = {k: v * 1e9 for k, v in s.idle_s.items()}
    # [20, 50]: fetch 20-25 (across the fetch's end), commit, decode inputs
    # around its nested COW copy, dispatch 40-45, the iteration's own 45-50;
    # [70, 90]: none 70-75, fetch 75-85, iteration 85-90; [110, 120]: none
    assert ns == pytest.approx({"fetch": 15.0, "sched": 25.0,
                                "dispatch": 5.0, "outside": 15.0})
    red = trace_reduce.reduce(_events())
    assert sum(s.idle_s.values()) == pytest.approx(
        red.window_s - red.busy_s, abs=1e-18)
    assert s.iterations == 2
    assert s.idle_ms("fetch") == pytest.approx(1000 * 15e-9 / 2)
    # iterations of 50 and 20 ns less their fetches of 15 and 10 ns
    assert s.host_step_s == pytest.approx([35e-9, 10e-9])
    assert s.host_step_ms() == pytest.approx(22.5e-6)
    # the one decode program paired ended before its fetch returned
    assert s.shift_s == [0.0]
    # labels: the serve span covering most, else the harness span
    gaps = serve_spans.label_gaps(_events(), s.idle)
    assert [g for g, _ in gaps] == ["serve.decode_inputs",
                                    "serve.fetch_tokens", "Engine.submit"]
    assert [t for _, t in gaps] == pytest.approx([30e-9, 20e-9, 10e-9])


def test_clocks_no_shift_reconciles_give_no_split():
    ev = _events()
    # the decode program starts before its dispatch returns and ends after
    # the fetch that waits on it: moving it either way breaks the other
    ev["devices"][0]["modules"][1] = ("jit_decode_step(1)", 42.0, 90.0)
    # in ms, so that the program found the device idle (not queued)
    ev = {k: [{**d, **{j: [(n, a * 1e6, b * 1e6) for n, a, b in d[j]]
                       for j in ("modules", "ops")}} for d in v]
          if k == "devices" else [(n, a * 1e6, b * 1e6) for n, a, b in v]
          for k, v in ev.items()}
    assert serve_spans.clock_shift(ev["devices"][0], ev["serve"]) is None
    assert serve_spans.split(ev) is None


def test_innermost_cuts_the_timeline_by_the_deepest_span():
    pieces = serve_spans.innermost([("a", 0.0, 10.0), ("b", 2.0, 4.0),
                                    ("c", 3.0, 12.0), ("d", 20.0, 21.0)])
    # c outlasts its parent b and is cut at b's end
    assert pieces == [("a", 0.0, 2.0), ("b", 2.0, 3.0), ("c", 3.0, 4.0),
                      ("a", 4.0, 10.0), ("d", 20.0, 21.0)]


def test_a_program_without_serve_spans_gives_no_split():
    ev = _events()
    ev["serve"] = []
    assert serve_spans.split(ev) is None
    assert serve_spans.split({"devices": [], "host": [],
                              "serve": _events()["serve"]}) is None


def _window(trace):
    return harness.Window(
        name="cell", conf=bench_smoke.conf(), cell={}, peaks={}, t0=0.0,
        t1=1.0, open_loop=False, records=[], stats0={}, stats1={},
        page_share=[], admit_lat=[], setup_programs=0, window_programs=0,
        steps=[], trace=trace)


def test_readers_read_the_split_of_the_cells_trace():
    w = _window(trace_reduce.reduce(_events()))
    got = {n: harness._reader("layer_metrics", n)(w) for n in READERS}
    assert got == pytest.approx({"idle_sched_ms": 12.5e-6,
                                 "idle_dispatch_ms": 2.5e-6,
                                 "idle_fetch_ms": 7.5e-6,
                                 "idle_outside_ms": 7.5e-6})
    assert harness._reader("layer_metrics", "host_step_ms")(w) == \
        pytest.approx(22.5e-6)
    # no device trace (the CPU), or a trace without serve spans: every
    # reader is silent
    ev = _events()
    ev["serve"] = []
    for win in (_window(None), _window(trace_reduce.reduce(ev))):
        for n in READERS + ("host_step_ms",):
            assert harness._reader("layer_metrics", n)(win) is None


@pytest.fixture(scope="module")
def chip_events():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_the_split_of_a_chip_trace_closes(chip_events):
    s = serve_spans.split(chip_events)
    red = trace_reduce.reduce(chip_events)
    assert 2 <= s.iterations <= 3
    assert s.window_s == pytest.approx(red.window_s)
    assert abs(sum(s.idle_s.values()) - (red.window_s - red.busy_s)) < 1e-9
    assert all(v >= 0.0 for v in s.idle_s.values())
    gaps = serve_spans.label_gaps(chip_events, s.idle)
    assert gaps and all(g.startswith("serve.") or g == "outside"
                        for g, _ in gaps)
    assert s.shift_s == [0.0]
    assert {n for n, _, _ in chip_events["serve"]} <= set(serve_spans.GROUP)
    # the host's own work in an iteration is shorter than the iteration
    assert 0.0 < s.host_step_ms() < 1000.0 * s.window_s / s.iterations
    # the renamed programs and kernels are still told apart
    assert red.decode_steps_s and \
        red.kernel_calls["paged_decode_attention"] > 0
    assert {m.split("(")[0] for d in chip_events["devices"]
            for m, _, _ in d["modules"]} >= {"jit_decode_step"}


def _moved(events, dt_ns):
    """The events with the device's times moved by ``dt_ns``."""
    out = dict(events)
    out["devices"] = [{**d, **{k: [(n, a + dt_ns, b + dt_ns)
                                   for n, a, b in d[k]]
                               for k in ("modules", "ops")}}
                      for d in events["devices"]]
    return out


def test_a_device_clock_placed_early_is_put_back(chip_events):
    # as in two traced runs of six on the chip: every device event 1.4 ms
    # early against the host, so each decode program seems to start before
    # its dispatch has returned
    synced = serve_spans.split(chip_events)
    s = serve_spans.split(_moved(chip_events, -1.4e6))
    # moved back but for the 0.2 ms a program starts after its dispatch
    assert 1.0e-3 < s.shift_s[0] < 1.4e-3
    for part in serve_spans.PARTS:
        assert s.idle_ms(part) == pytest.approx(synced.idle_ms(part),
                                                abs=0.25)
    red = trace_reduce.reduce(_moved(chip_events, -1.4e6))
    assert sum(s.idle_s.values()) == pytest.approx(
        red.window_s - red.busy_s, abs=1.4e-3)


def test_a_device_clock_placed_late_is_put_back(chip_events):
    # 5 ms late: each decode program seems to end after the fetch that
    # waits on it has returned; moved back to end just as it returns
    s = serve_spans.split(_moved(chip_events, 5e6))
    assert -5e-3 < s.shift_s[0] < -3e-3
    assert s.idle_ms("dispatch") > 0.0
