"""A configuration file in, the system under test's model and weights out.

``program_config`` maps the file's Hugging Face keys onto the program's
``ModelConfig`` for the architecture it names (``program_arch``) and checks
that nothing the file states is lost on the way. ``make_params`` makes the
weights from the seed on the device, in one jitted call, as float32 (the type
the program stores and serves them in), laid out as the program takes them.
The reference (``bench/reference``) reads the same arrays; the program's own
initialiser is never called, so nothing the reference reads was made by it.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import Dict

import jax
import jax.numpy as jnp

from repro import configs


def program_config(conf: Dict):
    """The program's ModelConfig for ``conf``, widths and depth from the
    file. Raises where the program's block would differ from what the file
    states."""
    base = configs.get_config(conf["program_arch"])
    (pattern, _), = base.groups
    hd = conf.get("head_dim") or conf["hidden_size"] // conf[
        "num_attention_heads"]
    cfg = dataclasses.replace(
        base, d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv=conf["num_key_value_heads"], d_ff=conf["intermediate_size"],
        vocab=conf["vocab_size"], head_dim=hd,
        groups=((pattern, conf["num_hidden_layers"]),),
        rope_theta=float(conf["rope_theta"]))
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


def _shapes(conf: Dict) -> Dict:
    """Leaf name -> (shape, kind) of the program's parameter tree."""
    d, H, K = (conf["hidden_size"], conf["num_attention_heads"],
               conf["num_key_value_heads"])
    hd = conf.get("head_dim") or d // H
    f, V, L = (conf["intermediate_size"], conf["vocab_size"],
               conf["num_hidden_layers"])
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
    return {"top": top, "layer": layer}


def _leaf(key, shape, kind):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return 1.0 + 0.1 * n
    if kind == "bias":
        return 0.1 * n
    if kind == "embed":
        return 0.02 * n
    return n / math.sqrt(kind)          # a matrix: 1/sqrt(fan_in)


def _nest(flat: Dict) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        *parents, name = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = v
    return out


def make_params(conf: Dict, seed: int):
    """The weights for ``seed``, made on the default device in one call."""
    shapes = _shapes(conf)

    def make(key):
        def leaves(group):
            return {path: _leaf(jax.random.fold_in(key, _path_id(path)),
                                shape, kind)
                    for path, (shape, kind) in shapes[group].items()}
        params = _nest(leaves("top"))
        params["groups"] = [(_nest(leaves("layer")),)]
        return params

    return jax.jit(make)(jax.random.PRNGKey(jax_seed(seed)))


def _path_id(path: str) -> int:
    return int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``seed``, which may exceed what 32 signed bits hold."""
    return int.from_bytes(
        hashlib.sha256(str(int(seed)).encode()).digest()[:4], "little") >> 1
