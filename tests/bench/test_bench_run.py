"""The harness end to end on the CPU at smoke sizes, and what it refuses.

A run here drives the program's engine through the harness's own set-up,
window and check, with the Pallas kernels in interpret mode and every size
cut (``bench_smoke.py``); the refusal tests run ``bench/run.py`` itself.
"""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import pytest

import bench_smoke
from bench_smoke import BENCH, ROOT, harness

import model  # noqa: E402  (bench/, on the path through bench_smoke)
from repro import configs
from repro.models import blocks, transformer

E2E_OPEN = {"setup_s", "output_tok_s"}


def _run_cli(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "minitron-4b-8l.batch-decode", "--seed", str(2**31 + 11),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_a_machine_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


@pytest.mark.parametrize("name", ["qwen2-0.5b", "minitron-4b-8l"])
def test_weights_are_laid_out_as_the_program_takes_them(name):
    conf = harness.load(BENCH / "configs" / f"{name}.json")
    block, _ = bench_smoke.block(name)
    cfg = block.program_config(conf)
    ours = jax.eval_shape(lambda: model.make_params(block.layout(conf), 5))
    theirs = jax.eval_shape(lambda: blocks.split_params(
        transformer.init_model(jax.random.PRNGKey(0), cfg))[0])
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(ours)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(theirs)]


def _program_config(name):
    return bench_smoke.block(name)[0].program_config(
        harness.load(BENCH / "configs" / f"{name}.json"))


def test_configs_keep_every_published_width():
    q = _program_config("qwen2-0.5b")
    base = configs.get_config("qwen2-0.5b")
    assert (q.d_model, q.n_heads, q.n_kv, q.hd, q.d_ff, q.vocab,
            q.groups) == (base.d_model, base.n_heads, base.n_kv, base.hd,
                          base.d_ff, base.vocab, base.groups)
    m = _program_config("minitron-4b-8l")
    base = configs.get_config("minitron-4b")
    assert (m.d_model, m.n_heads, m.n_kv, m.hd, m.d_ff, m.vocab) == \
        (3072, 24, 8, 128, 9216, 256000) == \
        (base.d_model, base.n_heads, base.n_kv, base.hd, base.d_ff,
         base.vocab)
    assert m.groups == ((("gqa:mlp",), 8),)
    assert m.mlp == "relu2" and not m.tie_embeddings


def test_a_cell_added_as_data_runs_end_to_end(tmp_path):
    """A new configuration, mix and cell are files and one entry: nothing
    that exists is edited, and the harness finds and runs the cell."""
    before = {p: p.read_bytes() for p in BENCH.rglob("*") if p.is_file()}
    root = tmp_path
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = "qwen2-smoke.chat-test"
    cell = bench_smoke.cell("chat")
    cell.update(name=name, config="qwen2-smoke", traffic="chat-test")
    (root / "bench" / "configs" / "qwen2-smoke.json").write_text(
        json.dumps(bench_smoke.conf()))
    (root / "bench" / "traffic" / "chat-test.json").write_text(
        json.dumps(bench_smoke.CHAT_MIX))
    (root / "bench" / "workloads" / f"{name}.json").write_text(
        json.dumps(cell))
    bench["workloads"].append({"name": name, "config": "qwen2-smoke",
                               "traffic": "chat-test", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    spec = harness.spec(name, root=root)
    res = harness.run(spec, 2**32 + 3, 3.0, False, time.perf_counter())
    assert res["correct"], res
    assert set(res["metrics"]) == E2E_OPEN
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert res["compared"]["worst_gap_sigma"]["value"] < \
        res["compared"]["worst_gap_sigma"]["limit"]
    assert {p: p.read_bytes() for p in BENCH.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts} == \
        {p: b for p, b in before.items() if "__pycache__" not in p.parts}


def test_closed_loop_traced_run_reports_its_layers(tmp_path):
    spec = bench_smoke.spec("batch")
    res = harness.run(spec, 17, 3.0, True, time.perf_counter(),
                      trace_dir=tmp_path)
    assert res["correct"], res
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["compiles_in_window"] == 0
    assert m["warmup_programs"] > 0
    assert 0 < m["decode_batch_mean"] <= 2
    assert 0 < m["kv_pages_used_share"] <= 100
    # no device trace on the CPU: the device readings are left out, never 0
    for name in ("mfu", "decode_step_ms", "decode_attn_roofline",
                 "device_idle_share"):
        assert name not in m
    assert "ttft_p50" not in m and "prefix_hit_share" not in m
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes", "busy_s", "window_s"}
