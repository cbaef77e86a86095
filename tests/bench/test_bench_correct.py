"""The comparison that decides ``correct``: the plain float32 reference
agrees with what the engine served, its control fails, and a run over a
broken timed path comes out not correct.

All on the CPU at smoke sizes (``bench_smoke.py``); the chip readings that
set the limit are in PERF.md.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_smoke
from bench_smoke import harness

import model  # noqa: E402  (bench/, on the path through bench_smoke)
from reference import check, common
from repro.serve import executor, paged_step

LIMIT = harness.load(bench_smoke.BENCH / "workloads" /
                     "minitron-4b-8l.batch-decode.json")["check"][
                         "worst_gap_sigma"]


@pytest.fixture
def fresh_programs(monkeypatch):
    """A fault planted in the program must be traced into new programs."""
    monkeypatch.setattr(executor, "_REGION_CACHE", {})
    jax.clear_caches()
    yield
    jax.clear_caches()


def _greedy(ref, params, dims, prompt, n, length):
    """The reference put in the program's place: greedy tokens, one padded
    forward per token (causal, so the padding is never seen)."""
    @jax.jit
    def step(p, seq, pos):
        h = ref.hidden(p, seq, dims)
        row = jax.lax.dynamic_index_in_dim(h, pos, keepdims=False)
        return jnp.argmax(jnp.matmul(row, ref.head(p, dims),
                                     precision=common.HIGHEST))
    seq = np.zeros(length, np.int32)
    seq[:len(prompt)] = prompt
    for i in range(n):
        pos = len(prompt) + i
        seq[pos] = int(step(params, jnp.asarray(seq), pos - 1))
    return seq[len(prompt):len(prompt) + n].tolist()


@pytest.mark.parametrize("config", ["qwen2-0.5b", "minitron-4b-8l"])
def test_control_in_fp8_fails_where_float32_reads_zero(config):
    conf = bench_smoke.conf(config)
    conf.update(hidden_size=256, intermediate_size=512, vocab_size=8192,
                num_hidden_layers=4)
    if conf.get("head_dim"):
        conf["head_dim"] = 64
    block, ref = bench_smoke.block(config)
    params = model.make_params(block.layout(conf), 3)
    dims = ref.Dims.of(conf)
    rng = np.random.default_rng(0)
    length = 96
    samples = []
    for _ in range(3):
        prompt = rng.integers(0, dims.vocab, 48).tolist()
        samples.append({"prompt": prompt,
                        "served": _greedy(ref, params, dims, prompt, 24,
                                          length)})
    f32 = np.concatenate(check.served_gaps(params, ref, dims, length,
                                           samples))
    ctrl = np.concatenate(check.served_gaps(params, ref, dims, length,
                                            samples, quant="fp8"))
    assert f32.max() == 0.0
    assert ctrl.max() > LIMIT


def test_served_tokens_agree_with_the_reference():
    res = harness.run(bench_smoke.spec("batch", "minitron-4b-8l"), 23, 2.0,
                      False, time.perf_counter())
    assert res["correct"], res
    assert res["compared"]["worst_gap_sigma"]["value"] < 0.1


def _planted(monkeypatch, fault):
    if fault == "token altered":
        real = executor.Executor.fetch_token_ids
        calls = {"n": 0}

        def altered(self, arrays):
            out = real(self, arrays)
            calls["n"] += 1
            if calls["n"] % 3 == 0:
                out = [(v + 1) % 512 for v in out]
            return out
        monkeypatch.setattr(executor.Executor, "fetch_token_ids", altered)
    elif fault == "state unchanged":
        # the decode step returns its pages as it got them: no K/V written
        monkeypatch.setattr(paged_step, "_scatter_token",
                            lambda pool, *a, **k: pool)


@pytest.mark.parametrize("fault", ["token altered", "state unchanged"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fresh_programs,
                                            fault):
    _planted(monkeypatch, fault)
    res = harness.run(bench_smoke.spec("batch"), 29, 2.0, False,
                      time.perf_counter())
    assert not res["correct"]
    assert res["compared"]["worst_gap_sigma"]["value"] > LIMIT
