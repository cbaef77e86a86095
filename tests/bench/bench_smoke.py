"""Smoke-size cells for the benchmark's CPU tests: the real cells' files
with every width, length and count cut so that a run takes seconds in the
Pallas interpreter. Nothing here is a measurement."""
import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402

SMOKE_WIDTHS = dict(hidden_size=64, num_attention_heads=4,
                    num_key_value_heads=2, intermediate_size=128,
                    num_hidden_layers=2, vocab_size=512)

CHAT_MIX = {
    "system_prompts": {"count": 2, "length": {"dist": "uniform", "lo": 20,
                                              "hi": 30}, "zipf_s": 1.0,
                       "primed": True},
    "turns": {"lo": 1, "hi": 3},
    "turn_gap_s": {"dist": "uniform_real", "lo": 0.5, "hi": 1.0},
    "prompt_len": {"dist": "lognormal", "median": 6, "sigma": 0.6, "lo": 2,
                   "hi": 12},
    "max_new": {"dist": "lognormal", "median": 4, "sigma": 0.5, "lo": 2,
                "hi": 6},
}
BATCH_MIX = {"prompt_len": {"dist": "loguniform", "lo": 4, "hi": 12},
             "max_new": {"dist": "loguniform", "lo": 8, "hi": 16}}


def conf(name="qwen2-0.5b"):
    c = harness.load(BENCH / "configs" / f"{name}.json")
    c.update(SMOKE_WIDTHS, name=f"{name}-smoke")
    if c.get("head_dim"):
        c["head_dim"] = 16
    return c


def block(name="qwen2-0.5b"):
    """The configuration's block: its program side and its reference."""
    return harness.block_of(BENCH / "configs" / f"{name}.json", conf(name))


def cell(kind="chat"):
    if kind == "chat":
        # an open-loop cell with the prefix cache: the harness's other path
        return {"name": "qwen2-0.5b.chat-smoke", "config": "qwen2-0.5b",
                "traffic": "chat-smoke",
                "load": {"kind": "open", "session_rate_per_s": 1.0,
                         "lead_s": 1},
                "engine": {"n_slots": 4, "max_seq": 96, "page_tokens": 8,
                           "n_pages": 48, "prefix": True,
                           "prefix_pages": 24, "token_budget": 8},
                "check": {"requests": 8, "worst_gap_sigma": 0.25}}
    c = harness.load(BENCH / "workloads" / "minitron-4b-8l.batch-decode.json")
    c["load"] = {"kind": "closed", "clients": 4, "requests_per_client": 4}
    c["engine"].update(n_slots=2, max_seq=32, page_tokens=8, n_pages=None,
                       token_budget=8)
    return c


def spec(kind="chat", config="qwen2-0.5b") -> "harness.Spec":
    c = cell(kind)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    ok = lambda m: "workloads" not in m or c["name"] in m["workloads"]
    peaks = harness.load(BENCH / "peaks.json")
    peaks["devices"]["cpu"] = copy.deepcopy(peaks["devices"]["TPU v5 lite"])
    program, ref = block(config)
    return harness.Spec(
        cell=c, conf=conf(config),
        mix=copy.deepcopy(CHAT_MIX if kind == "chat" else BATCH_MIX),
        end_to_end=[m for m in bench["end_to_end"] if ok(m)],
        per_layer=[m for m in bench["per_layer"] if ok(m)], peaks=peaks,
        block=program, ref=ref)
