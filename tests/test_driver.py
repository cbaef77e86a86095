"""Driver-level tests: failure injection + checkpoint-rollback recovery, and
the serving driver end-to-end."""
import os
import subprocess
import sys

import numpy as np
import pytest


def test_train_driver_recovers_from_injected_failure(tmp_path):
    from repro.launch.train import train
    losses = train("qwen2-0.5b", smoke=True, steps_total=12,
                   ckpt_dir=str(tmp_path), batch=4, seq=16, lr=1e-3,
                   ckpt_every=5, inject_failure=8)
    # 12 requested steps + replayed ones after rollback to step 5
    assert len(losses) >= 12
    assert np.isfinite(losses).all()
    # a checkpoint exists at the final step
    from repro.checkpoint.manager import CheckpointManager
    assert CheckpointManager(str(tmp_path)).latest_step() == 12


def test_train_driver_restart_resumes(tmp_path):
    from repro.launch.train import train
    train("qwen2-0.5b", smoke=True, steps_total=6, ckpt_dir=str(tmp_path),
          batch=4, seq=16, lr=1e-3, ckpt_every=3)
    # second invocation restores (elastic restart path) and continues
    losses = train("qwen2-0.5b", smoke=True, steps_total=9,
                   ckpt_dir=str(tmp_path), batch=4, seq=16, lr=1e-3,
                   ckpt_every=3)
    assert len(losses) == 3  # only steps 6..9 run


def test_grad_accum_matches_plain():
    """grad_accum=2 over 2×batch must track plain within tolerance."""
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.data import pipeline as dp
    from repro.models import blocks, transformer
    from repro.optim import adamw
    from repro.train import step as steps

    cfg = configs.get_smoke_config("qwen2-0.5b")
    params_t = transformer.init_model(jax.random.PRNGKey(0), cfg)
    params, _ = blocks.split_params(params_t)

    def mk_state():
        return steps.TrainState(params=params, opt=adamw.init(params),
                                step=jnp.zeros((), jnp.int32))

    dcfg = dp.DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8, seed=1)
    b = {k: jnp.asarray(v) for k, v in dp.make_batch(dcfg, 0).items()}
    ocfg = adamw.Config(lr=1e-3, warmup_steps=1)
    s1, m1 = jax.jit(steps.make_train_step(cfg, ocfg))(mk_state(), b)
    s2, m2 = jax.jit(steps.make_train_step(cfg, ocfg, grad_accum=2))(mk_state(), b)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-3)
    l1 = jax.tree_util.tree_leaves(s1.params)[0]
    l2 = jax.tree_util.tree_leaves(s2.params)[0]
    # Adam normalizes near-zero grads to ±lr-scale updates, so bf16 reduction
    # -order noise flips signs elementwise; the bound is ABSOLUTE: ≤ 2·lr·warm
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2.5e-3)


@pytest.mark.slow  # subprocess CLI end-to-end
@pytest.mark.parametrize("mode", ["dense", "paged", "tiered", "chunked",
                                  "prefix", "tp", "trace", "fleet"])
def test_serve_driver_cli(mode, tmp_path):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    trace_out = str(tmp_path / "serve.trace.json")
    cmd = [sys.executable, "-m", "repro.launch.serve", "--smoke",
           "--requests", "3", "--slots", "2", "--max-new", "3",
           "--max-seq", "32"]
    if mode == "paged":
        cmd += ["--paged", "--page-tokens", "8"]
    elif mode == "tiered":
        # 2 pages force oversubscription → at least one preemptive swap
        cmd += ["--tiered", "--page-tokens", "8", "--pages", "2",
                "--host-budget-mb", "1"]
    elif mode == "chunked":
        cmd += ["--chunked-prefill", "--page-tokens", "8",
                "--token-budget", "6"]
    elif mode == "prefix":
        # a shared 8-token system prompt → the 2nd/3rd requests must hit
        cmd += ["--prefix-cache", "--page-tokens", "8", "--token-budget", "8",
                "--shared-prefix-len", "8", "--prompt-len", "2"]
    elif mode == "tp":
        # the tensor-parallel path needs ≥2 devices: force host devices in
        # the subprocess (the qwen2 smoke config has n_kv=2, so tp=2 works)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=2"
                            ).strip()
        cmd += ["--tp", "2", "--chunked-prefill", "--page-tokens", "8",
                "--token-budget", "6"]
    elif mode == "trace":
        # tiered oversubscription so swap DMA windows land in the export
        cmd += ["--tiered", "--page-tokens", "8", "--pages", "2",
                "--host-budget-mb", "1", "--trace", trace_out,
                "--metrics-log", "7"]
    elif mode == "fleet":
        # two replicas with prefix-aware routing on a shared system prompt
        cmd += ["--replicas", "2", "--prefix-cache", "--page-tokens", "8",
                "--token-budget", "8", "--shared-prefix-len", "8",
                "--prompt-len", "2"]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=400)
    assert "3 requests" in r.stdout, r.stdout + r.stderr
    if mode == "paged":
        assert "admission refusals" in r.stdout
    elif mode == "tiered":
        assert "preemptions" in r.stdout and "swap out" in r.stdout
    elif mode == "chunked":
        assert "token budget 6" in r.stdout and "prefill chunks" in r.stdout
    elif mode == "prefix":
        assert "prefix hits" in r.stdout and "shared tokens" in r.stdout
    elif mode == "tp":
        assert "serve:tp2+chunked" in r.stdout, r.stdout + r.stderr
    elif mode == "trace":
        assert "[serve:trace]" in r.stdout and "stall%" in r.stdout, \
            r.stdout + r.stderr
        assert "[metrics]" in r.stdout       # final-window flush at drain
        import json as _json
        doc = _json.load(open(trace_out))
        assert doc["traceEvents"] and doc["displayTimeUnit"] == "ms"
    elif mode == "fleet":
        assert "[serve:fleet] 2 replicas (prefix router)" in r.stdout, \
            r.stdout + r.stderr
        assert "routed 3" in r.stdout and "gen 1" in r.stdout


def test_validate_bench_schema_roundtrip(tmp_path):
    """The CI schema gate: a well-formed sectioned BENCH file passes; a
    missing section, a NaN, and truncated JSON each fail with a pointed
    error (so a malformed bench write fails the workflow)."""
    import json
    import sys as _sys
    _sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.validate_bench import validate, ENGINE_NUM_KEYS, SCHEMAS

    def engine_stub(section):
        return {k: 1.0 for k in ENGINE_NUM_KEYS[section]}

    good = {
        "tiering": {"arch": "qwen2-0.5b", "hot_pages": 4, "page_tokens": 8,
                    "n_slots": 2, "requests": 12,
                    "concurrent_pages_needed": 24,
                    "throughput_tok_per_s": 25.8, "peak_hbm_bytes": 8192,
                    "admitted_seq_count": 12, "swap_overhead_ratio": 1.4,
                    "reference_untiered_large": engine_stub("tiering"),
                    "untiered_hot_only": engine_stub("tiering"),
                    "tiered": engine_stub("tiering")},
        "chunked_prefill": {"arch": "qwen2-0.5b", "token_budget": 12,
                            "n_slots": 6, "page_tokens": 8, "n_pages": 17,
                            "requests": 6, "late_arrivals": 4,
                            "ttft_speedup": 4.2, "stall_p99_ratio": 1.1,
                            "monolithic": engine_stub("chunked_prefill"),
                            "chunked": engine_stub("chunked_prefill")},
        "prefix_cache": {"arch": "qwen2-0.5b", "token_budget": 24,
                         "n_slots": 4, "page_tokens": 8, "n_pages": 60,
                         "requests": 10, "prefix_len": 64,
                         "prefill_token_reduction": 6.5, "ttft_speedup": 12.0,
                         "baseline": engine_stub("prefix_cache"),
                         "prefix": engine_stub("prefix_cache")},
        "tensor_parallel": {"arch": "qwen2-0.5b", "n_kv": 4,
                            "page_tokens": 8, "n_pages": 24, "n_slots": 4,
                            "token_budget": 14, "requests": 8,
                            "identical_streams": 1,
                            "tp1": engine_stub("tensor_parallel"),
                            "tp2": engine_stub("tensor_parallel"),
                            "tp4": engine_stub("tensor_parallel")},
        "slo": {"arch": "qwen2-0.5b", "hot_pages": 4, "page_tokens": 8,
                "n_slots": 2, "requests": 12, "interactive_requests": 4,
                "itl_target_s": 0.02, "itl_uncontended_p50_s": 0.001,
                "baseline_refusals": 29, "slo_refusals": 0,
                "shed_total": 6, "shed_overload": 4, "shed_deadline": 2,
                "baseline_itl_p99_s": 1.05, "slo_itl_p99_s": 0.002,
                "identical_streams": 1,
                "reference": engine_stub("slo"),
                "baseline": engine_stub("slo"), "slo": engine_stub("slo")},
        "trace": {"arch": "qwen2-0.5b", "hot_pages": 4, "page_tokens": 8,
                  "n_slots": 2, "requests": 12, "tp": 2, "token_budget": 10,
                  "plain_wall_s": 0.5, "identical_streams": 1,
                  "deterministic_snapshot": 1, "closure_worst_err_pct": 0.0,
                  "trace_json": "BENCH_serve.trace.json",
                  "traced": engine_stub("trace")},
        "overlap": {"arch": "qwen2-0.5b", "hot_pages": 4, "page_tokens": 8,
                    "n_slots": 2, "requests": 12, "tp": 2,
                    "token_budget": 10, "identical_streams": 1,
                    "noncompute_stall_reduction": 3.0,
                    "sync": engine_stub("overlap"),
                    "overlap": engine_stub("overlap")},
        "fleet": {"arch": "qwen2-0.5b", "token_budget": 24, "n_slots": 4,
                  "page_tokens": 8, "n_pages": 60, "replicas": 2,
                  "tenants": 2, "requests": 12, "prefix_len": 48,
                  "prefill_token_reduction": 1.6, "ttft_speedup": 1.2,
                  "single": engine_stub("fleet"),
                  "round_robin": engine_stub("fleet"),
                  "prefix": engine_stub("fleet")},
        "kv_quant": {"arch": "qwen2-0.5b", "page_tokens": 8, "hot_pages": 4,
                     "n_slots": 2, "requests": 12,
                     "hbm_budget_bytes": 1 << 20,
                     "page_nbytes_f32": 4096, "page_nbytes_int8": 1088,
                     "resident_seqs_f32": 4, "resident_seqs_int8": 15,
                     "residency_gain": 3.75, "swap_bytes_f32": 98304,
                     "swap_bytes_int8": 26112, "swap_byte_reduction": 3.76,
                     "token_match_rate": 0.97, "max_abs_logit_err": 0.01,
                     "f32": engine_stub("kv_quant"),
                     "int8": engine_stub("kv_quant")},
    }
    p = tmp_path / "BENCH_serve.json"
    p.write_text(json.dumps(good))
    assert validate(str(p)) == []
    # missing section
    p.write_text(json.dumps({"tiering": good["tiering"]}))
    assert any("chunked_prefill" in e for e in validate(str(p)))
    assert any("prefix_cache" in e for e in validate(str(p)))
    # NaN numeric field
    bad = dict(good)
    bad["chunked_prefill"] = dict(good["chunked_prefill"],
                                  ttft_speedup=float("nan"))
    p.write_text(json.dumps(bad))
    assert any("ttft_speedup" in e for e in validate(str(p)))
    # truncated JSON
    p.write_text(json.dumps(good)[:40])
    assert any("unreadable" in e for e in validate(str(p)))
    # the committed artifact itself must be valid
    repo_bench = os.path.join(os.path.dirname(__file__), "..",
                              "BENCH_serve.json")
    assert validate(repo_bench) == []
    assert set(SCHEMAS) == {"tiering", "chunked_prefill", "prefix_cache",
                            "tensor_parallel", "slo", "trace", "overlap",
                            "fleet", "kv_quant"}


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    """Entry points keep JAX's persistent compilation cache where
    JAX_COMPILATION_CACHE_DIR says, and otherwise at the fixed in-checkout
    directory (never a temporary one)."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "path = enable_compile_cache()\n"
        "assert jax.config.jax_compilation_cache_dir == path\n"
        "print(path)\n")
    if env_dir:    # cache even a fast compile, then look for the entry
        script += (
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(4.0)).block_until_ready()\n")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    path = r.stdout.strip().splitlines()[-1]
    if env_dir:
        assert path == str(tmp_path)
        assert os.listdir(tmp_path)
    else:
        assert path == os.path.join(os.path.realpath(repo), ".jax_cache")
