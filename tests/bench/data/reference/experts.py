"""The float32 reference of the tests' expert block (``blocks/experts.py``):
GQA attention with rotary, RMSNorm, a gated silu MLP in the dense layers and,
in the others, the softmax over the top ``num_experts_per_tok`` router
logits weighting those experts' gated silu MLPs."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from reference.common import HIGHEST, mm as _mm, rms as _rms, rope as _rope


@dataclasses.dataclass(frozen=True)
class Dims:
    n_heads: int
    n_kv: int
    hd: int
    top_k: int
    eps: float
    theta: float

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        d, H = conf["hidden_size"], conf["num_attention_heads"]
        return cls(n_heads=H, n_kv=conf["num_key_value_heads"],
                   hd=conf.get("head_dim") or d // H,
                   top_k=conf["num_experts_per_tok"],
                   eps=float(conf["rms_norm_eps"]),
                   theta=float(conf["rope_theta"]))


def hidden(params, tokens, dims: Dims, quant: Optional[str] = None):
    mm = _mm(quant)
    f32 = lambda a: a.astype(jnp.float32)
    L = tokens.shape[0]
    H, K, hd = dims.n_heads, dims.n_kv, dims.hd
    causal = jnp.tril(jnp.ones((L, L), bool))

    def attend(x, p):
        a = p["mixer"]
        h = _rms(x, p["ln1"]["scale"], dims.eps)
        q = _rope(mm(h, a["wq"]).reshape(L, H, hd), dims.theta)
        k = _rope(mm(h, a["wk"]).reshape(L, K, hd), dims.theta)
        v = mm(h, a["wv"]).reshape(L, K, hd)
        k, v = jnp.repeat(k, H // K, 1), jnp.repeat(v, H // K, 1)
        s = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) / jnp.sqrt(
            jnp.float32(hd))                                     # [H, L, L]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm(w, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(L, H * hd)
        x = x + mm(o, a["wo"])
        return x, _rms(x, p["ln2"]["scale"], dims.eps)

    def dense(x, p):
        p = jax.tree.map(f32, p)
        x, h = attend(x, p)
        m = p["ffn"]
        return x + mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]),
                      m["w_down"]), None

    def experts(x, p):
        p = jax.tree.map(f32, p)
        x, h = attend(x, p)
        m = p["ffn"]
        logits = mm(h, m["router"])                              # [L, E]
        top, idx = jax.lax.top_k(logits, dims.top_k)
        gate = jnp.zeros_like(logits).at[
            jnp.arange(L)[:, None], idx].set(jax.nn.softmax(top, -1))
        ein = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)
        up = jax.nn.silu(ein("ld,edf->elf", h, m["w_gate"])) * \
            ein("ld,edf->elf", h, m["w_up"])
        y = ein("elf,efd->eld", up, m["w_down"])
        return x + ein("le,eld->ld", gate, y), None

    x = f32(params["embed"])[tokens]
    x, _ = jax.lax.scan(dense, x, params["groups"][0][0])
    x, _ = jax.lax.scan(experts, x, params["groups"][1][0])
    return _rms(x, f32(params["final_norm"]["scale"]), dims.eps)


def head(params, dims: Dims):
    return params["lm_head"].astype(jnp.float32)
