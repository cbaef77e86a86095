"""The readers of the end-to-end and per-layer metrics, on windows built by
hand: what they count, and what they leave out."""
import numpy as np
import pytest

import bench_smoke
from bench_smoke import harness

import trace_reduce  # noqa: E402  (bench/, on the path through bench_smoke)
from repro.serve.engine import Request


def _record(due, t_first=0.0, tokens=(), done=False, prompt_len=10):
    req = Request(seq_id=0, prompt=np.zeros(prompt_len, np.int32))
    req.t_first = t_first
    req.t_tokens = list(tokens)
    req.tokens_out = [0] * len(tokens)
    req.done = done
    return harness.Record(req=req, due=due)


def _window(records, **kw):
    base = dict(name="w", conf=bench_smoke.conf(), cell={}, peaks=harness.load(
        bench_smoke.BENCH / "peaks.json"), t0=10.0, t1=20.0, open_loop=True,
        records=records, stats0={}, stats1={}, page_share=[], admit_lat=[],
        setup_programs=0, window_programs=0, steps=[],
        device_kind="TPU v5 lite", block=bench_smoke.block()[0])
    base.update(kw)
    return harness.Window(**base)


def _read(kind, name, w):
    return harness._reader(kind, name)(w)


def test_tails_count_requests_left_unfinished_at_the_close():
    served = [_record(10.0 + i, t_first=10.5 + i, tokens=[10.5 + i])
              for i in range(8)]
    # due at 18 s and 19 s, no first token by the close at 20 s: they enter
    # with 2 s and 1 s, the longest and third-longest waits
    waiting = [_record(18.0), _record(19.0)]
    # due before the window opened: not a request of this window
    early = [_record(9.0)]
    w = _window(served + waiting + early)
    assert sorted(w.ttfts()) == pytest.approx([0.5] * 8 + [1.0, 2.0])
    assert _read("layer_metrics", "ttft_p95", w) == pytest.approx(
        1000 * np.percentile([0.5] * 8 + [1.0, 2.0], 95))
    assert _read("layer_metrics", "ttft_p50", w) == pytest.approx(500.0)
    assert harness.attempted(w) == 10


def test_gaps_and_rate_are_of_the_window_alone():
    r = _record(5.0, t_first=9.0, tokens=[9.0, 10.5, 12.0, 12.1, 21.0])
    w = _window([r])
    # 9.0 -> 10.5 lands inside; 12.1 -> 21.0 lands after the close
    assert sorted(w.gaps()) == pytest.approx([0.1, 1.5, 1.5])
    for kind, name in (("metrics", "itl_p95"),
                       ("layer_metrics", "itl_p95_open")):
        assert _read(kind, name, w) == pytest.approx(
            1000 * np.percentile([1.5, 1.5, 0.1], 95))
    assert _read("metrics", "output_tok_s", w) == pytest.approx(3 / 10.0)


def test_device_readings_are_left_out_without_a_trace():
    w = _window([_record(11.0, t_first=11.5, tokens=[11.5, 11.6])])
    for name in ("mfu", "prefill_mfu", "decode_step_ms",
                 "decode_attn_roofline", "prefill_attn_roofline",
                 "prefill_ms_per_ktok", "device_idle_share"):
        assert _read("layer_metrics", name, w) is None


def test_prefill_mfu_counts_the_chunks_the_window_dispatched():
    red = trace_reduce.Reduction(
        window_s=1.0, busy_s=0.5, decode_steps_s=[], prefill_programs_s=0.25,
        kernel_s={}, kernel_calls={}, op_self_s={}, gaps=[])
    log = [{"chunks": [(0, 0, 8), (1, 8, 8)]}]
    w = _window([], steps=[(12.0, 12.1, log), (25.0, 25.1, log)],
                trace=red, trace_t0=11.0, trace_t1=13.0)
    import costs
    s = costs.Shape.of(w.conf, w.block.layer_flops(w.conf))
    flops = 16 * s.matmul_flops + costs.prefill_attn(s, 0, 16)[0]
    assert _read("layer_metrics", "prefill_mfu", w) == pytest.approx(
        100 * flops / (0.25 * 197e12))
    assert _read("layer_metrics", "prefill_ms_per_ktok", w) == \
        pytest.approx(1e6 * 0.25 / 16)


def test_an_unknown_device_kind_is_an_error():
    red = trace_reduce.Reduction(
        window_s=1.0, busy_s=0.5, decode_steps_s=[0.01], prefill_programs_s=0,
        kernel_s={"paged_decode_attention": 0.1}, kernel_calls={},
        op_self_s={}, gaps=[])
    w = _window([_record(11.0, t_first=11.5, tokens=[11.5, 11.6])],
                trace=red, trace_t0=11.0, trace_t1=12.0, device_kind="TPU v9")
    with pytest.raises(ValueError, match="no peaks"):
        _read("layer_metrics", "mfu", w)


def test_submit_lateness_is_of_requests_due_in_the_window():
    recs = [_record(10.0 + i) for i in range(4)] + [_record(5.0)]
    for i, r in enumerate(recs):
        r.t_submit = r.due + 0.01 * (i + 1)
    w = _window(recs)
    assert _read("layer_metrics", "submit_late_p95", w) == pytest.approx(
        1000 * np.percentile([0.01, 0.02, 0.03, 0.04], 95))
