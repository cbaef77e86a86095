"""The benchmark's FLOP and byte counts against hand arithmetic."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

import costs  # noqa: E402
from blocks import gqa  # noqa: E402


def conf(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def shape(name):
    return costs.Shape.of(conf(name), gqa.layer_flops(conf(name)))


def test_qwen2_per_token_counts():
    s = shape("qwen2-0.5b")
    # q and o: 2*2*896*896; k and v: 2*2*896*128; gated MLP: 3*2*896*4864
    assert gqa.layer_flops(conf("qwen2-0.5b")) == \
        [3_211_264 + 458_752 + 26_148_864] * 24
    assert s.layers == 24 and s.matmul_flops == 715_653_120
    assert costs.head_flops(s) == 2 * 896 * 151_936 == 272_269_312
    assert costs.attn_flops(s, 1000) == 24 * 4 * 14 * 64 * 1000
    # live K/V is 12 KiB per token (2 kv heads x 64 x bf16 x k,v x 24)
    assert costs.decode_attn_bytes(s, 1) - costs.decode_attn_bytes(s, 0) \
        == 12_288
    assert costs.decode_attn_bytes(s, 1000) == 24 * (512_000 + 7_168)


def test_minitron_per_token_counts():
    s = shape("minitron-4b-8l")
    # q, o: 2*2*3072*3072; k, v: 2*2*3072*1024; relu2 MLP: 2*2*3072*9216
    assert gqa.layer_flops(conf("minitron-4b-8l")) == 8 * [
        37_748_736 + 12_582_912 + 113_246_208]
    assert costs.head_flops(s) == 1_572_864_000
    # 32 KiB of K/V per token at 8 layers
    assert costs.decode_attn_bytes(s, 1) - costs.decode_attn_bytes(s, 0) \
        == 32_768


def test_prefill_chunk_is_causal_and_chunking_independent():
    s = shape("qwen2-0.5b")
    f, _ = costs.prefill_attn(s, 100, 50)
    # positions 100..149 attend over 101..150 keys: 50*100 + 50*51/2 pairs
    assert f == 24 * 4 * 14 * 64 * 6275
    whole, _ = costs.prefill_attn(s, 0, 300)
    parts = sum(costs.prefill_attn(s, a, 100)[0] for a in (0, 100, 200))
    assert whole == parts


def test_count_and_least_time():
    s = shape("qwen2-0.5b")
    w = costs.count(s, decode_contexts=[10, 20], chunks=[(0, 8)],
                    emitted=3)
    per_tok = 715_653_120
    attn = costs.attn_flops(s, 10) + costs.attn_flops(s, 20)
    assert w.model_flops == (2 + 8) * per_tok + attn + \
        costs.prefill_attn(s, 0, 8)[0] + 3 * 272_269_312
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_time(1000.0, 50.0, peak) == (10.0, "compute")
    assert costs.least_time(100.0, 50.0, peak) == (5.0, "memory")
    assert pytest.approx(w.decode_attn_bytes) == \
        costs.decode_attn_bytes(s, 10) + costs.decode_attn_bytes(s, 20)
