"""The block seam: a configuration names its block, and the weights, the
reference and the work count come from that block's two modules.

The gqa block is held to what the benchmark read before the seam
(``data/gqa_readings.json``: weight hashes, gaps and work counts), and a
block of the tests' own (``data/blocks/experts.py``,
``data/reference/experts.py``), with a dense group and an expert group, is
added to a copy of the benchmark as files and found by name.
"""
import hashlib
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_smoke
from bench_smoke import BENCH, ROOT, harness

import costs  # noqa: E402  (bench/, on the path through bench_smoke)
import model  # noqa: E402
from reference import check, common  # noqa: E402

DATA = ROOT / "tests" / "bench" / "data"
BEFORE = json.loads((DATA / "gqa_readings.json").read_text())
CONFIGS = ["minitron-4b-8l", "qwen2-0.5b"]


def _samples():
    rng = np.random.default_rng(5)
    return [{"prompt": rng.integers(0, 512, n).tolist(),
             "served": rng.integers(0, 512, m).tolist()}
            for n, m in ((9, 6), (20, 11))]


def _sha256(params):
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", CONFIGS)
def test_gqa_weights_are_the_same_bit_for_bit(name):
    block, _ = bench_smoke.block(name)
    params = model.make_params(block.layout(bench_smoke.conf(name)),
                               2**31 + 7)
    assert _sha256(params) == BEFORE["make_params_sha256"][name]


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("name", CONFIGS)
def test_gqa_gaps_are_the_same(name, quant):
    block, ref = bench_smoke.block(name)
    conf = bench_smoke.conf(name)
    params = model.make_params(block.layout(conf), 2**31 + 7)
    gaps = check.served_gaps(params, ref, ref.Dims.of(conf), 32, _samples(),
                             quant=quant)
    want = BEFORE["served_gaps"][f"{name}.{quant}"]
    assert [g.tolist() for g in gaps] == want


@pytest.mark.parametrize("name", CONFIGS)
def test_gqa_work_count_is_the_same(name):
    conf = harness.load(BENCH / "configs" / f"{name}.json")
    block, _ = bench_smoke.block(name)
    w = costs.count(costs.Shape.of(conf, block.layer_flops(conf)),
                    [5, 100, 1000, 2063], [(0, 7), (7, 300), (512, 64)], 9)
    assert vars(w) == BEFORE["count"][name]


EXPERTS = dict(
    name="experts-test", block="experts", hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_hidden_layers=3, first_k_dense_replace=1, num_local_experts=6,
    num_experts_per_tok=2, intermediate_size=32, dense_intermediate_size=96,
    vocab_size=512, rms_norm_eps=1e-6, rope_theta=10000.0)


def _root_with(tmp_path, conf):
    """A copy of the benchmark with the tests' expert block, ``conf`` and a
    cell on it added as files."""
    root = tmp_path
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("blocks", "reference"):
        shutil.copy(DATA / kind / "experts.py", root / "bench" / kind)
    (root / "bench" / "configs" / "experts-test.json").write_text(
        json.dumps(conf))
    cell = bench_smoke.cell("batch")
    cell.update(name="experts-test.batch", config="experts-test")
    (root / "bench" / "workloads" / "experts-test.batch.json").write_text(
        json.dumps(cell))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "experts-test.batch",
                               "config": "experts-test",
                               "traffic": cell["traffic"], "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_a_block_added_as_files_is_found_by_name(tmp_path):
    spec = harness.spec("experts-test.batch", root=_root_with(tmp_path,
                                                              EXPERTS))
    conf = spec.conf
    layout = spec.block.layout(conf)
    params = model.make_params(layout, 11)
    # a dense group, then an expert group with its leaves per expert
    dense, moe = params["groups"]
    assert moe[0]["ffn"]["w_gate"].shape == (2, 6, 64, 32)
    assert moe[0]["ffn"]["router"].shape == (2, 64, 6)
    assert dense[0]["ffn"]["w_gate"].shape == (1, 64, 96)
    # the groups' leaves of one path are drawn apart
    assert not np.allclose(dense[0]["mixer"]["wq"][0],
                           moe[0]["mixer"]["wq"][0])
    with pytest.raises(ValueError, match="serves no expert block"):
        spec.block.program_config(conf)

    # the check reads the block's own reference: its greedy tokens read 0
    dims = spec.ref.Dims.of(conf)
    prompt = list(range(3, 15))
    seq = jnp.asarray(prompt, jnp.int32)
    served = []
    for _ in range(5):
        h = spec.ref.hidden(params, seq, dims)
        tok = jnp.argmax(jnp.matmul(h[-1], spec.ref.head(params, dims),
                                    precision=common.HIGHEST))
        served.append(int(tok))
        seq = jnp.concatenate([seq, tok[None].astype(jnp.int32)])
    sample = [{"prompt": prompt, "served": served}]
    gaps, = check.served_gaps(params, spec.ref, dims, 24, sample)
    assert gaps.shape == (5,) and gaps.max() == 0.0
    wrong, = check.served_gaps(params, spec.ref, dims, 24, [
        {"prompt": prompt, "served": [(t + 1) % 512 for t in served]}])
    assert wrong.min() > 0.0

    # the work count takes each layer's matrix products from the block
    shape = costs.Shape.of(conf, spec.block.layer_flops(conf))
    attn = 2 * 2 * 64 * 64 + 2 * 2 * 64 * 32
    assert shape.layers == 3
    assert shape.matmul_flops == (attn + 3 * 2 * 64 * 96) + 2 * (
        attn + 2 * 64 * 6 + 2 * 3 * 2 * 64 * 32)
    w = costs.count(shape, [10], [(0, 12)], 1)
    assert w.model_flops == 13 * shape.matmul_flops + \
        costs.attn_flops(shape, 10) + costs.prefill_attn(shape, 0, 12)[0] + \
        costs.head_flops(shape)


@pytest.mark.parametrize("fault", ["no block key", "no such block"])
def test_a_configuration_without_its_block_is_an_error(tmp_path, fault):
    conf = dict(EXPERTS)
    if fault == "no block key":
        del conf["block"]
    else:
        conf["block"] = "absent"
    with pytest.raises(ValueError, match="experts-test.json") as e:
        harness.spec("experts-test.batch", root=_root_with(tmp_path, conf))
    assert ("block" in str(e.value) if fault == "no block key"
            else "absent.py" in str(e.value))
