"""End-to-end behaviour tests: checkpointing, data determinism, serving
engine, offload mailbox, DMA API, gradient compression, and a real
loss-goes-down training run."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.core import dma
from repro.core.offload import Mailbox, TargetRegion
from repro.data import pipeline as dp
from repro.models import blocks, transformer
from repro.optim import adamw
from repro.parallel import compression
from repro.train import step as steps


# --------------------------------------------------------------------------
# checkpoint
# --------------------------------------------------------------------------
def _tiny_state(seed=0):
    cfg = configs.get_smoke_config("qwen2-0.5b")
    params_t = transformer.init_model(jax.random.PRNGKey(seed), cfg)
    params, _ = blocks.split_params(params_t)
    return cfg, steps.TrainState(params=params, opt=adamw.init(params),
                                 step=jnp.zeros((), jnp.int32))


def test_checkpoint_roundtrip(tmp_path):
    cfg, state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(7, state, extra={"data_step": 7})
    restored, extra = mgr.restore(state)
    assert extra["data_step"] == 7
    a = jax.tree_util.tree_leaves(state.params)[0]
    b = jax.tree_util.tree_leaves(restored.params)[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_async_and_gc(tmp_path):
    _, state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3):
        mgr.save(s, state, blocking=False)
    mgr.wait()
    assert mgr.list_steps() == [2, 3]  # keep=2 enforced


def test_checkpoint_ignores_partial(tmp_path):
    """A crash mid-save (no MANIFEST) must be invisible to restore."""
    _, state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state)
    broken = tmp_path / "step_00000002"
    broken.mkdir()
    (broken / "shard_00000.npy").write_bytes(b"junk")   # no manifest
    assert mgr.latest_step() == 1
    restored, _ = mgr.restore(state)
    assert restored is not None


def test_checkpoint_shape_mismatch_raises(tmp_path):
    _, state = _tiny_state()
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, state)
    bad = jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape + (2,)) if x.ndim == 2 else x, state)
    with pytest.raises(ValueError):
        mgr.restore(bad)


# --------------------------------------------------------------------------
# data pipeline
# --------------------------------------------------------------------------
def test_data_deterministic_skip_ahead():
    cfg = dp.DataConfig(vocab=512, seq_len=32, global_batch=4)
    b10a = dp.make_batch(cfg, 10)
    b10b = dp.make_batch(cfg, 10)
    np.testing.assert_array_equal(b10a["tokens"], b10b["tokens"])
    # restart at step 10 == skipping 10 steps
    it = dp.make_batches(cfg, start_step=10)
    np.testing.assert_array_equal(next(it)["tokens"], b10a["tokens"])
    # different hosts see different data
    cfg2 = dp.DataConfig(vocab=512, seq_len=32, global_batch=4, n_hosts=2,
                         host_id=1)
    assert not np.array_equal(dp.make_batch(cfg2, 10)["tokens"][:2],
                              b10a["tokens"][:2])


def test_data_labels_shifted():
    cfg = dp.DataConfig(vocab=512, seq_len=32, global_batch=2, mtp=True)
    b = dp.make_batch(cfg, 0)
    np.testing.assert_array_equal(b["labels"], b["next_tokens"])
    assert b["tokens"].shape == (2, 32)
    assert b["mtp_labels"].shape == (2, 32)


# --------------------------------------------------------------------------
# offload: mailbox + target region
# --------------------------------------------------------------------------
def test_mailbox_fifo_and_drain():
    mb = Mailbox(depth=3)
    assert mb.put(1) and mb.put(2) and mb.put(3)
    assert not mb.put(4)          # full → sender retries (paper semantics)
    assert mb.get() == 1
    assert mb.drain(10) == [2, 3]
    assert mb.get(timeout=0.01) is None


def test_target_region_compile_cache():
    tr = TargetRegion(lambda x: x * 2 + 1, name="t")
    spec = jax.ShapeDtypeStruct((8,), jnp.float32)
    l1, c1 = tr.lower_compile(spec)
    l2, c2 = tr.lower_compile(spec)
    assert c1 is c2               # cache hit
    assert tr.stats.n_compiles == 1
    out = tr(jnp.ones(8))
    np.testing.assert_allclose(np.asarray(out), 3.0)
    assert tr.stats.n_offloads == 1


# --------------------------------------------------------------------------
# DMA API
# --------------------------------------------------------------------------
def test_hero_memcpy_roundtrip():
    x = np.arange(64, dtype=np.float32)
    dev = dma.hero_memcpy_host2dev(None, x)
    h = dma.hero_memcpy_dev2host_async(dev)
    back = dma.hero_memcpy_wait(h)
    np.testing.assert_array_equal(np.asarray(back), x)


def test_memcpy2d_ref_semantics():
    src = np.arange(64, dtype=np.float32)
    dst = np.zeros(64, np.float32)
    # gather 4 rows of 8 elems with stride 16 → packed rows of 8
    out = dma.memcpy2d_ref(dst, src, rows=4, elems=8, src_stride=16,
                           dst_stride=8)
    for r in range(4):
        np.testing.assert_array_equal(out[r * 8:(r + 1) * 8],
                                      src[r * 16:r * 16 + 8])


# --------------------------------------------------------------------------
# gradient compression
# --------------------------------------------------------------------------
def test_int8_quant_error_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
    q, scale = compression.quantize_int8(g, 256)
    deq = compression.dequantize_int8(q, scale, g.shape)
    err = np.abs(np.asarray(deq - g))
    assert err.max() <= float(np.abs(np.asarray(g)).max()) / 127.0 + 1e-6


def test_error_feedback_preserves_sum():
    """Over many steps, EF compensates quantization bias: Σ out ≈ Σ g."""
    rng = np.random.default_rng(1)
    comp = compression.Compressor(mode="int8", block=64)
    g = {"w": jnp.asarray(rng.standard_normal(64).astype(np.float32) * 1e-3)}
    resid = compression.init_residual(g)
    total = np.zeros(64, np.float32)
    for _ in range(50):
        out, resid = compression.with_error_feedback(comp, g, resid)
        total += np.asarray(out["w"])
    expect = np.asarray(g["w"]) * 50
    assert np.abs(total - expect).max() <= np.abs(expect).max() * 0.1 + 1e-4


# --------------------------------------------------------------------------
# serving engine (continuous batching over the mailbox)
# --------------------------------------------------------------------------
def _run_engine(cfg, params, paged: bool, prompts, max_new=4, n_slots=2,
                max_seq=64):
    from repro.serve.engine import Engine, Request
    eng = Engine(cfg, params, n_slots=n_slots, max_seq=max_seq, paged=paged)
    for i, p in enumerate(prompts):
        assert eng.submit(Request(seq_id=i, prompt=p.copy(), max_new=max_new))
    done = eng.run(max_steps=200)
    return eng, done


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_serves_batched_requests(paged):
    cfg = configs.get_smoke_config("qwen2-0.5b")
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, 5).astype(np.int32)
               for _ in range(5)]
    eng, done = _run_engine(cfg, params, paged, prompts)
    assert len(done) == 5
    for r in done:
        assert len(r.tokens_out) >= 4
        assert all(0 <= t < cfg.vocab for t in r.tokens_out)
    assert eng.stats["prefills"] == 5
    assert max(eng.stats["batch_occupancy"]) == 1.0  # batching really happened


def test_engine_paged_matches_dense_greedy_streams():
    """The acceptance bar for the paged serving path: the same request
    stream must produce identical greedy token streams in both cache
    regimes, and a full paged run must leak no pages."""
    cfg = configs.get_smoke_config("qwen2-0.5b")
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, 6).astype(np.int32)
               for _ in range(4)]
    streams = {}
    engines = {}
    for paged in (False, True):
        eng, done = _run_engine(cfg, params, paged, prompts, max_new=5)
        assert len(done) == 4
        streams[paged] = {r.seq_id: r.tokens_out for r in done}
        engines[paged] = eng
    assert streams[True] == streams[False]
    pool = engines[True].pool
    assert pool.alloc.free_pages == pool.alloc.n_pages   # nothing leaked
    assert engines[True].stats["peak_used_bytes"] > 0
    assert engines[True].stats["peak_used_bytes"] <= \
        engines[False].pool.footprint_bytes()


def test_engine_tiered_oversubscription_matches_paged_streams():
    """Acceptance bar for the tiered KV cache: with the hot tier sized to K
    pages, a workload needing > 2K pages of concurrent KV (which the
    untiered paged engine refuses to hold concurrently) completes with
    greedy token streams identical to the untiered paged path, via
    preemptive swap to host DRAM — and leaks nothing in either tier."""
    from repro.serve.engine import Engine, Request
    cfg = configs.get_smoke_config("qwen2-0.5b")
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)
    rng = np.random.default_rng(2)
    # K=4 hot pages of 8 tokens; 6 requests × 2 worst-case pages = 12 > 2K
    K, n_req = 4, 6
    prompts = [rng.integers(0, cfg.vocab, 6).astype(np.int32)
               for _ in range(n_req)]

    def go(**kw):
        eng = Engine(cfg, params, n_slots=2, max_seq=64, page_tokens=8, **kw)
        for i, p in enumerate(prompts):
            assert eng.submit(Request(seq_id=i, prompt=p.copy(), max_new=5))
        done = eng.run(max_steps=1000)
        return eng, {r.seq_id: r.tokens_out for r in done}

    eng_ref, ref = go(paged=True, n_pages=4 * K)    # holds everything at once
    eng_t, tier = go(tiered=True, n_pages=K)
    assert len(tier) == n_req                       # workload completes
    assert tier == ref                              # bit-identical streams
    s = eng_t.stats_summary()
    assert s["preemptions"] > 0 and s["swap_in_count"] > 0
    assert s["swap_out_bytes"] == s["swap_in_bytes"] > 0
    assert s["peak_in_system"] * 2 > 2 * K          # true oversubscription
    assert s["peak_host_bytes"] > 0
    assert s["queue_lat_p99_s"] >= s["queue_lat_p50_s"] > 0
    # nothing leaked in either tier
    pool = eng_t.pool
    assert pool.alloc.free_pages == pool.alloc.n_pages
    assert pool.cold_seqs() == [] and pool.hero.levels[3].in_use() == 0
    # the untiered engine at the same K refuses the concurrency
    eng_u, unt = go(paged=True, n_pages=K)
    assert unt == ref
    assert eng_u.stats["admission_refusals"] > 0
    assert eng_u.stats["peak_in_system"] <= 2


# --------------------------------------------------------------------------
# training actually learns (synthetic structured stream)
# --------------------------------------------------------------------------
@pytest.mark.slow  # 30-step training loop
def test_loss_decreases_on_synthetic_stream():
    cfg = configs.get_smoke_config("qwen2-0.5b")
    dcfg = dp.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, seed=3)
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)
    state = steps.TrainState(params=params, opt=adamw.init(params),
                             step=jnp.zeros((), jnp.int32))
    fn = jax.jit(steps.make_train_step(
        cfg, adamw.Config(lr=3e-3, warmup_steps=5, total_steps=60)))
    losses = []
    for s in range(30):
        b = dp.make_batch(dcfg, s)
        state, m = fn(state, {k: jnp.asarray(v) for k, v in b.items()})
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.5, losses


# --------------------------------------------------------------------------
# pipeline parallelism: numerical equivalence (8 fake devices, subprocess)
# --------------------------------------------------------------------------
PIPE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.parallel.pipeline import gpipe

mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(jax.sharding.AxisType.Auto,))
L, D, M, mb = 8, 16, 8, 4
rng = np.random.default_rng(0)
ws = jnp.asarray(rng.standard_normal((L, D, D)).astype(np.float32) * 0.3)
xs = jnp.asarray(rng.standard_normal((M, mb, D)).astype(np.float32))

def layer_fn(w, x):
    return jnp.tanh(x @ w)

apply = gpipe(layer_fn, mesh, "stage", L)
got = apply(ws, xs)

def seq(x):
    for i in range(L):
        x = layer_fn(ws[i], x)
    return x
exp = jax.vmap(seq)(xs)
np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-5, atol=1e-5)
print("PIPE_OK")
"""


@pytest.mark.slow  # 8-fake-device subprocess
def test_gpipe_equivalence_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__),
                                                   "..", "src"))
    r = subprocess.run([sys.executable, "-c", PIPE_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "PIPE_OK" in r.stdout, r.stdout + r.stderr


def test_engine_chunked_prefill_matches_monolithic_streams():
    """Acceptance bar for continuous batching with chunked prefill: the same
    request mix through the unified token-budgeted step loop must produce
    greedy token streams bit-identical to the monolithic-prefill paged
    engine, while never exceeding the budget in any iteration."""
    from repro.serve.engine import Engine, Request
    cfg = configs.get_smoke_config("qwen2-0.5b")
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab, int(L)).astype(np.int32)
               for L in (6, 13, 3, 9)]

    def go(**kw):
        eng = Engine(cfg, params, n_slots=3, max_seq=64, page_tokens=8, **kw)
        for i, p in enumerate(prompts):
            assert eng.submit(Request(seq_id=i, prompt=p.copy(), max_new=5))
        done = eng.run(max_steps=500)
        return eng, {r.seq_id: list(r.tokens_out) for r in done}

    _, mono = go(paged=True)
    eng_c, chk = go(chunked_prefill=True, token_budget=8)
    assert chk == mono
    assert eng_c.stats["prefill_chunks"] > len(prompts), "prompts were sliced"
    assert eng_c.stats["prefill_chunk_tokens"] == sum(len(p) for p in prompts)
    for entry in eng_c.stats["iter_log"]:
        assert entry["decode_tokens"] + entry["prefill_tokens"] <= 8
    s = eng_c.stats_summary()
    assert s["max_iter_tokens"] <= s["token_budget"] == 8
    assert s["ttft_p50_s"] > 0
    pool = eng_c.pool
    assert pool.alloc.free_pages == pool.alloc.n_pages
    assert pool._reserved == {}


def test_engine_tiered_chunked_midprefill_preemption_resumes_at_offset():
    """Tiered-path regression: preempt a request mid-prefill, swap it to
    host DRAM, resume it, and assert it continues from its chunk offset
    (never re-prefilled) with a bit-exact greedy stream."""
    from repro.serve.engine import Engine, Request
    cfg = configs.get_smoke_config("qwen2-0.5b")
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)
    rng = np.random.default_rng(2)
    # long prompt (4 pages of 8) + competitors on a 6-page hot pool: the
    # long request is preempted mid-prefill when the shorts arrive behind it
    lens = (30, 10, 10, 6)
    prompts = [rng.integers(0, cfg.vocab, L).astype(np.int32) for L in lens]

    def go(**kw):
        eng = Engine(cfg, params, n_slots=2, max_seq=64, page_tokens=8, **kw)
        for i, p in enumerate(prompts):
            assert eng.submit(Request(seq_id=i, prompt=p.copy(), max_new=4))
        done = eng.run(max_steps=2000)
        return eng, {r.seq_id: list(r.tokens_out) for r in done}

    _, ref = go(paged=True, n_pages=32)          # holds everything at once
    eng_t, tier = go(tiered=True, chunked_prefill=True, token_budget=6,
                     n_pages=6)
    assert tier == ref                           # bit-exact streams
    s = eng_t.stats_summary()
    assert s["preempted_mid_prefill"] > 0, "a mid-prefill preemption occurred"
    assert s["swap_in_count"] > 0
    # resumed at the chunk offset: total chunk tokens == total prompt tokens
    # (a re-prefill would recount the preempted prefix)
    assert s["prefill_chunk_tokens"] == sum(lens)
    assert s["evictions_reprefill"] == 0
    pool = eng_t.pool
    assert pool.alloc.free_pages == pool.alloc.n_pages
    assert pool.cold_seqs() == [] and pool.hero.levels[3].in_use() == 0


@pytest.mark.parametrize("kw", [dict(), dict(paged=True),
                                dict(tiered=True),
                                dict(chunked_prefill=True)],
                         ids=["dense", "paged", "tiered", "chunked"])
def test_stats_summary_empty_engine(kw):
    """stats_summary() must report zeros on an engine that never served a
    request — empty counter lists (queue latency, TTFT, occupancy, iteration
    log) must not reach numpy aggregations."""
    from repro.serve.engine import Engine
    cfg = configs.get_smoke_config("qwen2-0.5b")
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)
    eng = Engine(cfg, params, n_slots=2, max_seq=32, page_tokens=8, **kw)
    assert eng.run(max_steps=3) == []            # idle run is a no-op
    s = eng.stats_summary()
    assert s["decode_steps"] == 0 and s["prefills"] == 0
    assert s["mean_occupancy"] == 0.0
    for p in (50, 90, 99):
        assert s[f"queue_lat_p{p}_s"] == 0.0
        assert s[f"ttft_p{p}_s"] == 0.0
    if kw.get("chunked_prefill"):
        assert s["max_iter_tokens"] == 0
    for v in s.values():
        assert np.isfinite(v), s
