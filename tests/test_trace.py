"""Execution-tracer tests (serve/trace.py) + the engine tracing contracts.

Unit level, against a fake clock (every tracer time value is deterministic):
the golden Chrome trace-event schema (``ph``/``ts``/``dur``/``pid``/``tid``/
``cat`` fields, metadata-first ordering, span nesting reflected in the
timestamps), exclusive-bucket exactness (the self-time decomposition sums to
the iteration span bit-exactly), ring-buffer eviction (oldest events drop
first, ``dropped`` counts them), and the request-lifecycle state machine
(prior state closes when the next opens; terminal states pop the track).

Engine level: the observe-only contract — tracing on/off produces
bit-identical greedy streams and equal ``stats_summary()`` counters; a
disabled tracer adds ZERO clock reads, so fake-clock twin engines (trace
off) produce bit-identical ``metrics_snapshot()`` JSON (the satellite-2
unified-clock gate); the exported trace file is valid JSON with the
expected track metadata; and stall buckets close every iteration's wall
time exactly.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import dma
from repro.models import blocks, transformer
from repro.serve import trace as T
from repro.serve.cache import CacheConfig
from repro.serve.engine import Engine, EngineConfig, Request

_CFG = configs.get_smoke_config("qwen2-0.5b", compute_dtype=jnp.float32)
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        params_t = transformer.init_model(jax.random.PRNGKey(0), _CFG)
        _PARAMS, _ = blocks.split_params(params_t)
    return _PARAMS


class FakeClock:
    """Deterministic monotonic clock: each read advances a fixed step."""

    def __init__(self, step: float = 0.001):
        self.t = 0.0
        self.step = step
        self.reads = 0

    def __call__(self) -> float:
        self.t += self.step
        self.reads += 1
        return self.t


# --------------------------------------------------------------------------
# tracer unit tests (fake clock)
# --------------------------------------------------------------------------
def test_chrome_trace_golden_schema():
    clk = FakeClock(step=1.0)        # 1 s per read -> 1e6 us deltas
    tr = T.Tracer(enabled=True, clock=clk)
    with tr.iteration():
        with tr.span("schedule"):
            pass
        with tr.span("fetch_tokens", arrays=2):
            pass
    tr.request_state(7, "queued")
    tr.request_state(7, "finished")          # close the track into the ring
    tr.async_span("dma", "swap_out_dma", clk(), clk(), bytes=4096, n=2)
    tr.instant("drain")
    doc = tr.chrome_trace()

    assert set(doc) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["dropped_events"] == 0
    assert doc["otherData"]["iterations"] == 1
    ev = doc["traceEvents"]

    # metadata first: process_name, then one thread_name per known track
    assert ev[0]["ph"] == "M" and ev[0]["name"] == "process_name"
    meta = [e for e in ev if e["ph"] == "M" and e["name"] == "thread_name"]
    labels = {e["tid"]: e["args"]["name"] for e in meta}
    assert labels[T.TID_ENGINE] == "engine"
    assert labels[T.TID_DMA] == "dma"
    assert labels[T.TID_REQ_BASE + 7] == "req 7"
    n_meta = 1 + len(meta)
    assert all(e["ph"] == "M" for e in ev[:n_meta])
    assert all(e["ph"] != "M" for e in ev[n_meta:])

    # complete events: schema + nesting (children inside the iteration span)
    xs = {e["name"]: e for e in ev if e["ph"] == "X"}
    assert set(xs) >= {"iteration", "schedule", "fetch_tokens", "queued"}
    for e in xs.values():
        assert set(e) >= {"ph", "name", "cat", "ts", "dur", "pid", "tid"}
        assert e["pid"] == 0
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
    for name in ("iteration", "schedule", "fetch_tokens"):
        assert xs[name]["tid"] == T.TID_ENGINE
    assert xs["queued"]["tid"] == T.TID_REQ_BASE + 7
    it, sch = xs["iteration"], xs["schedule"]
    assert it["cat"] == "iteration" and sch["cat"] == "phase"
    assert it["ts"] <= sch["ts"]
    assert sch["ts"] + sch["dur"] <= it["ts"] + it["dur"]
    assert xs["fetch_tokens"]["args"]["arrays"] == 2
    # fake clock: 1 s per read -> every span is an exact multiple of 1e6 us
    assert sch["dur"] == pytest.approx(1e6)

    # async pair: matching id, begin before end, dma track
    b = next(e for e in ev if e["ph"] == "b")
    e_ = next(e for e in ev if e["ph"] == "e")
    assert b["id"] == e_["id"] and b["tid"] == T.TID_DMA
    assert b["ts"] < e_["ts"] and b["args"]["bytes"] == 4096

    # instants carry thread scope
    inst = next(e for e in ev if e["ph"] == "i" and e["name"] == "drain")
    assert inst["s"] == "t"

    # the whole document is json-serialisable (Perfetto-loadable)
    json.loads(json.dumps(doc))


def test_ring_buffer_evicts_oldest_and_counts_drops():
    tr = T.Tracer(enabled=True, clock=FakeClock(), buffer=4)
    for i in range(10):
        tr.instant(f"ev{i}")
    assert tr.dropped == 6
    assert [e["name"] for e in tr.events] == ["ev6", "ev7", "ev8", "ev9"]
    assert tr.stats() == {"events": 4, "dropped": 6, "iterations": 0}
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 6


def test_ring_wrap_marks_orphaned_parents_partial():
    # ring wraps mid-iteration: children evicted while the parent's X
    # survives — export must mark such parents partial so readers never
    # assume exact child closure on a wrapped window
    tr = T.Tracer(enabled=True, clock=FakeClock(), buffer=6)
    with tr.iteration():                     # iter 0: 5 children + parent
        for name in ("schedule", "policy", "dispatch", "fetch_tokens",
                     "cow_copy"):
            with tr.span(name):
                pass
    with tr.iteration():                     # iter 1 evicts iter-0 children
        with tr.span("schedule"):
            pass
    assert tr.dropped == 2
    doc = tr.chrome_trace()
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    iters = sorted((e for e in xs if e["cat"] == "iteration"),
                   key=lambda e: e["ts"])
    assert len(iters) == 2
    assert iters[0]["args"].get("partial") is True, \
        "an iteration whose children were evicted must be marked partial"
    assert "partial" not in iters[1]["args"]
    for e in xs:                             # the intact iteration's spans
        if e["ts"] >= iters[1]["ts"]:
            assert "partial" not in e["args"]
    # export never mutates the ring: a second export is identical
    assert tr.chrome_trace() == doc
    # an unwrapped ring marks nothing
    tr2 = T.Tracer(enabled=True, clock=FakeClock(), buffer=64)
    with tr2.iteration():
        with tr2.span("schedule"):
            pass
    assert all("partial" not in e["args"]
               for e in tr2.chrome_trace()["traceEvents"]
               if e.get("ph") == "X")


def test_shadowed_bucket_attributes_in_flight_host_work():
    # a host span that opens after device_dispatch() and closes before
    # device_landed() is overlapped work -> "shadowed", not its usual bucket
    tr = T.Tracer(enabled=True, clock=FakeClock(step=0.5))
    with tr.iteration():
        tr.device_dispatch()
        with tr.span("schedule"):            # fully under the in-flight step
            pass
        with tr.span("swap_wait", dir="in"):  # dma wait hidden by the step
            pass
        with tr.span("fetch_tokens"):        # the sync point: never shadowed
            tr.device_landed()
        with tr.span("schedule"):            # after landing: a real stall
            pass
    b = tr.last_iteration()["buckets"]
    assert set(b) == set(T.BUCKETS)
    assert b["shadowed"] > 0.0 and b["dma"] == 0.0
    assert b["fetch"] > 0.0 and b["schedule"] > 0.0
    assert sum(b.values()) == pytest.approx(tr.last_iteration()["dur"],
                                            rel=1e-12)
    # a span still open when the step lands is NOT shadowed (it outlived
    # the overlap window)
    tr2 = T.Tracer(enabled=True, clock=FakeClock(step=0.5))
    with tr2.iteration():
        tr2.device_dispatch()
        with tr2.span("schedule"):
            tr2.device_landed()
    b2 = tr2.last_iteration()["buckets"]
    assert b2["shadowed"] == 0.0 and b2["schedule"] > 0.0


def test_bucket_self_time_decomposition_is_exact():
    tr = T.Tracer(enabled=True, clock=FakeClock(step=0.5))
    with tr.iteration():
        with tr.span("schedule"):
            with tr.span("swap_wait", dir="in"):     # nested: dma bucket
                pass
        with tr.span("policy"):
            pass
        with tr.span("prefill_chunk"):
            with tr.span("dispatch", kind="prefill_chunk"):
                pass
        with tr.span("fetch_tokens"):
            pass
    entry = tr.last_iteration()
    assert entry["iter"] == 0
    b = entry["buckets"]
    assert set(b) == set(T.BUCKETS)
    assert all(v >= 0.0 for v in b.values())
    # exclusive self-times: exact closure, not approximate
    assert sum(b.values()) == pytest.approx(entry["dur"], rel=1e-12)
    # nested swap_wait lands in dma, its parent keeps only its self-time
    assert b["dma"] > 0.0 and b["schedule"] > 0.0 and b["fetch"] > 0.0
    # policy maps into the schedule bucket; dispatch/prefill_chunk into other
    assert b["other"] > 0.0


def test_stall_summary_percentages_sum_to_100():
    tr = T.Tracer(enabled=True, clock=FakeClock())
    for _ in range(5):
        with tr.iteration():
            with tr.span("schedule"):
                pass
    s = tr.stall_summary()
    assert s["iterations"] == 5
    total = (s["stall_pct_schedule"] + s["stall_pct_fetch"]
             + s["stall_pct_dma"] + s["stall_pct_other"])
    assert total == pytest.approx(100.0, rel=1e-9)
    # empty tracer reports zeros, never NaN
    empty = T.Tracer(enabled=True, clock=FakeClock()).stall_summary()
    assert empty["iterations"] == 0 and empty["stall_pct_schedule"] == 0.0


def test_request_lifecycle_state_machine():
    clk = FakeClock()
    tr = T.Tracer(enabled=True, clock=clk)
    tr.request_state(3, "queued")
    tr.request_state(3, "queued")            # re-assert: no-op
    tr.request_state(3, "prefill")           # closes queued
    tr.request_state(3, "decode")
    tr.request_state(3, "finished")          # terminal: close + instant + pop
    ev = list(tr.events)
    xs = [e for e in ev if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["queued", "prefill", "decode"]
    assert all(e["cat"] == "request" and e["tid"] == T.TID_REQ_BASE + 3
               for e in xs)
    # contiguous: each state opens where the prior closed (the ring holds
    # seconds under "t"; chrome_trace converts to us "ts" on export)
    for a, b in zip(xs, xs[1:]):
        assert a["t"] + a["dur"] == pytest.approx(b["t"])
    inst = [e for e in ev if e["ph"] == "i"]
    assert [e["name"] for e in inst] == ["finished"]
    # popped: a fresh lifecycle can start over
    tr.request_state(3, "queued")
    assert 3 in tr._req_open


def test_null_tracer_is_inert_but_keeps_time():
    tr = T.null_tracer()
    assert not tr.enabled
    assert tr.now() > 0.0                    # the clock works when disabled
    with tr.span("schedule"):
        with tr.iteration():
            pass
    tr.request_state(1, "queued")
    tr.async_span("dma", "x", 0.0, 1.0)
    tr.instant("y")
    assert len(tr.events) == 0 and tr.dropped == 0
    assert tr.last_iteration() is None
    assert T.null_tracer() is tr             # module singleton


# --------------------------------------------------------------------------
# engine-level contracts
# --------------------------------------------------------------------------
def _mk(trace_on=False, clock=None):
    return Engine(_CFG, _params(), config=EngineConfig(
        n_slots=2, max_seq=64, chunked=True, token_budget=10,
        preempt_quantum=1, trace=trace_on, clock=clock,
        cache=CacheConfig(paged=True, tiered=True, page_tokens=8, n_pages=8,
                          host_budget_bytes=1 << 22)))


def _drive(eng, n_req=5):
    rng = np.random.default_rng(0)
    for i in range(n_req):
        assert eng.submit(Request(
            seq_id=i, prompt=rng.integers(1, _CFG.vocab, 9).astype(np.int32),
            max_new=6))
    done = eng.run(max_steps=500)
    return {r.seq_id: list(r.tokens_out) for r in done}


def test_tracing_is_observe_only_streams_and_counters():
    s_off = _drive(_mk(trace_on=False))
    s_on = _drive(_mk(trace_on=True))
    assert s_off == s_on and len(s_off) == 5


def test_traced_stats_summary_counters_match_untraced():
    a, b = _mk(trace_on=False), _mk(trace_on=True)
    _drive(a), _drive(b)
    sa, sb = a.stats_summary(), b.stats_summary()
    for k in sa:
        if k.endswith("_s"):                 # wall-clock fields may differ
            continue
        assert sa[k] == sb[k], f"counter {k} diverged under tracing"


def test_fake_clock_twins_snapshot_bit_identical():
    # trace OFF + injected clock: zero extra clock reads vs an untraced
    # engine, so two independent runs must produce the same timing values
    snaps = []
    for _ in range(2):
        eng = _mk(trace_on=False, clock=FakeClock())
        _drive(eng)
        snaps.append(json.dumps(eng.metrics_snapshot(), sort_keys=True))
    assert snaps[0] == snaps[1]
    assert "stall_pct" not in snaps[0]       # stall hists are trace-gated


def test_transfer_handles_stamp_their_own_clock():
    # satellite regression: the clock is per-handle, not a process global —
    # a handle built against clock A never reads clock B
    clk1, clk2 = FakeClock(step=1.0), FakeClock(step=100.0)
    h1 = dma.hero_memcpy_host2dev_async(None, np.ones(4, np.float32),
                                        clock=clk1)
    h2 = dma.hero_memcpy_host2dev_async(None, np.ones(4, np.float32),
                                        clock=clk2)
    h1.wait(), h2.wait()
    assert h1.t_start == 1.0 and h1.t_done == 2.0
    assert h2.t_start == 100.0 and h2.t_done == 200.0
    # default clock still works (and wait() stays idempotent)
    h3 = dma.hero_memcpy_host2dev_async(None, np.ones(4, np.float32))
    h3.wait()
    done = h3.t_done
    assert 0.0 < h3.t_start <= done
    h3.wait()
    assert h3.t_done == done


def test_dma_clock_scoped_per_engine():
    # two live engines with different injected clocks: driving one must
    # never read the other's clock (the old module-global _CLOCK meant the
    # last-constructed engine stamped everyone's transfers)
    clk_a, clk_b = FakeClock(), FakeClock()
    a = _mk(trace_on=True, clock=clk_a)
    b = _mk(trace_on=True, clock=clk_b)      # built later: would have stolen
    before_b = clk_b.reads
    sa = _drive(a)
    assert clk_b.reads == before_b, "engine A's transfers read B's clock"
    before_a = clk_a.reads
    sb = _drive(b)
    assert clk_a.reads == before_a, "engine B's transfers read A's clock"
    assert sa == sb
    # the oversubscribed tiered mix really swapped (property not vacuous)
    assert a.scheduler.pool.swap_out_count > 0
    assert b.scheduler.pool.swap_out_count > 0


def test_traced_engine_stall_closure_and_export(tmp_path):
    eng = _mk(trace_on=True, clock=FakeClock())
    streams = _drive(eng)
    assert len(streams) == 5
    log = eng.tracer.stall_log()
    assert log, "a traced run must record iterations"
    for e in log:
        assert all(v >= 0.0 for v in e["buckets"].values())
        assert sum(e["buckets"].values()) == pytest.approx(e["dur"],
                                                           rel=1e-9)
    snap = eng.metrics_snapshot()
    assert all(f"stall_pct_{b}" in snap["histograms"] for b in T.BUCKETS)

    path = eng.trace_export(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    names = {e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"}
    assert {"iteration", "schedule", "dispatch", "fetch_tokens",
            "swap_wait"} <= names
    async_names = {e["name"] for e in doc["traceEvents"]
                   if e.get("ph") == "b"}
    assert "swap_out_dma" in async_names


def test_programs_carry_stable_names():
    # a profile names each program jit_<function>, so the decode and the
    # prefill-chunk steps are told apart (tests/test_tpu_compile.py checks
    # the kernels' names on the chip)
    eng = Engine(_CFG, _params(), config=EngineConfig(
        n_slots=2, max_seq=32, chunked=True, token_budget=8,
        cache=CacheConfig(paged=True, page_tokens=8)))
    ex, pool = eng.executor, eng.pool
    tables = jnp.asarray(pool.device_page_tables())
    dec = ex._decode._jitted.lower(
        ex.params, jnp.zeros((2, 1), jnp.int32), pool.pages, tables,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool)).as_text()
    chunk = ex._prefill_chunk._jitted.lower(
        ex.params, jnp.zeros((1, 5), jnp.int32), pool.pages, tables[0],
        jnp.zeros((), jnp.int32)).as_text()
    assert dec.startswith("module @jit_decode_step ")
    assert chunk.startswith("module @jit_prefill_chunk_step ")


def test_span_cost_script_times_each_case():
    from benchmarks.span_cost import measure
    got = measure(loops=50)
    for side in ("off_us", "session_us"):
        assert set(got[side]) == {"span", "span_meta", "dispatch",
                                  "iteration"}
        assert all(v > 0.0 for v in got[side].values())
