"""The dense GQA decoder block as the program runs it (``("gqa:mlp",)``):
GQA attention with rotary, RMSNorm, and a gated silu or squared-ReLU MLP.

What the harness needs from the program's side for a configuration that
names ``"block": "gqa"``:

  program_config(conf)  the program's ``ModelConfig``, widths and depth from
                        the file; raises where the program's block differs
  layout(conf)          the weight leaves as (shape, kind), for the top level
                        and for each group of stacked layers
  layer_flops(conf)     matrix-product FLOPs one token needs in each layer

Its float32 reference is ``bench/reference/gqa.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax.numpy as jnp

from repro import configs


def _dims(conf: Dict):
    d, H = conf["hidden_size"], conf["num_attention_heads"]
    return (d, H, conf["num_key_value_heads"], conf.get("head_dim") or d // H,
            conf["intermediate_size"], conf["vocab_size"],
            conf["num_hidden_layers"])


def program_config(conf: Dict):
    """The program's ModelConfig for ``conf``, widths and depth from the
    file. Raises where the program's block would differ from what the file
    states."""
    base = configs.get_config(conf["program_arch"])
    (pattern, _), = base.groups
    d, H, K, hd, f, V, L = _dims(conf)
    cfg = dataclasses.replace(
        base, d_model=d, n_heads=H, n_kv=K, d_ff=f, vocab=V, head_dim=hd,
        groups=((pattern, L),), rope_theta=float(conf["rope_theta"]))
    act = {"silu": "swiglu", "relu2": "relu2"}[conf["hidden_act"]]
    want = dict(mlp=act, qkv_bias=bool(conf.get("attention_bias", False)),
                tie_embeddings=bool(conf["tie_word_embeddings"]),
                norm="rms", window=None, logit_softcap=None,
                embed_scale=False, sandwich_norm=False,
                zero_centered_norm=False, compute_dtype=jnp.bfloat16)
    got = {k: getattr(cfg, k) for k in want}
    if got != want or pattern != ("gqa:mlp",):
        raise ValueError(f"{conf['name']}: the program's block is {got} "
                         f"{pattern}, the file states {want}")
    eps = conf.get("rms_norm_eps", conf.get("norm_eps"))
    if eps != 1e-6 or conf.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError(f"{conf['name']}: the program normalises with eps "
                         "1e-6 and rotates every head dimension")
    return cfg


def layout(conf: Dict) -> Dict:
    """Leaf path -> (shape, kind) of the program's parameter tree: ``top``,
    and ``groups``, one list of pattern positions per group (here one group
    of one position, every layer alike)."""
    d, H, K, hd, f, V, L = _dims(conf)
    layer = {"ln1/scale": ((L, d), "norm"), "ln2/scale": ((L, d), "norm"),
             "mixer/wq": ((L, d, H * hd), d), "mixer/wk": ((L, d, K * hd), d),
             "mixer/wv": ((L, d, K * hd), d),
             "mixer/wo": ((L, H * hd, d), H * hd)}
    if conf.get("attention_bias"):
        layer.update({"mixer/bq": ((L, H * hd), "bias"),
                      "mixer/bk": ((L, K * hd), "bias"),
                      "mixer/bv": ((L, K * hd), "bias")})
    if conf["hidden_act"] == "silu":
        layer.update({"ffn/w_gate": ((L, d, f), d), "ffn/w_up": ((L, d, f), d),
                      "ffn/w_down": ((L, f, d), f)})
    else:
        layer.update({"ffn/w_in": ((L, d, f), d), "ffn/w_out": ((L, f, d), f)})
    top = {"embed": ((V, d), "embed"), "final_norm/scale": ((d,), "norm")}
    if not conf["tie_word_embeddings"]:
        top["lm_head"] = ((d, V), d)
    return {"top": top, "groups": [[layer]]}


def layer_flops(conf: Dict) -> List[int]:
    """Projections and MLP of each layer, for one token: 2mkn a product."""
    d, H, K, hd, f, _, L = _dims(conf)
    mlp_mats = 3 if conf["hidden_act"] == "silu" else 2
    qo = 2 * 2 * d * H * hd
    kv = 2 * 2 * d * K * hd
    return [qo + kv + 2 * mlp_mats * d * f] * L
