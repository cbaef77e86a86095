"""Plain float32 forward of a decoder-only GQA transformer, from its config.

Straight ``jax.numpy`` with every matrix product at ``Precision.HIGHEST``:
no kernels, no cache, no paging, no batching. It reads the architecture from
the configuration file (Hugging Face keys) and the weights from the pytree
the benchmark made from the seed (``bench/model.py``), never from the program.

The block, as the configuration states it:

  x = embed[tokens]
  per layer:  h = rms(x) * ln1;  q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
              rotary (half-split) on q and k at positions 0..L-1
              causal softmax(q k^T / sqrt(hd)) v over the group's kv head
              x += attn Wo;  h = rms(x) * ln2
              x += silu(h Wg) * (h Wu) Wd     (hidden_act "silu")
                or relu(h Wi)^2 Wo            (hidden_act "relu2")
  logits = (rms(x) * final) head,  head = embed^T when tied

``quant`` names a lower precision for the control (``"fp8"``): each operand
of every matrix product is rounded to float8 e4m3 with a per-tensor scale
(amax / 448) before the product, as an fp8 serving path would store it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    n_heads: int
    n_kv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    eps: float
    theta: float
    act: str
    bias: bool
    tied: bool

    @classmethod
    def of(cls, conf: dict) -> "Dims":
        d, H = conf["hidden_size"], conf["num_attention_heads"]
        return cls(d=d, n_heads=H, n_kv=conf["num_key_value_heads"],
                   hd=conf.get("head_dim") or d // H,
                   ff=conf["intermediate_size"], vocab=conf["vocab_size"],
                   layers=conf["num_hidden_layers"],
                   eps=float(conf.get("rms_norm_eps", conf.get("norm_eps"))),
                   theta=float(conf["rope_theta"]), act=conf["hidden_act"],
                   bias=bool(conf.get("attention_bias", False)),
                   tied=bool(conf["tie_word_embeddings"]))


def _fp8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(quant: Optional[str]):
    if quant is None:
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)
    if quant == "fp8":
        return lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)
    raise ValueError(f"unknown control precision {quant!r}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [L, heads, hd]; rotate the two halves by position."""
    L, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv       # [L, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(params, tokens, dims: Dims, quant: Optional[str] = None):
    """Final-normed hidden states [L, d] of one sequence ``tokens`` [L]."""
    mm = _mm(quant)
    f32 = lambda a: a.astype(jnp.float32)
    L = tokens.shape[0]
    H, K, hd = dims.n_heads, dims.n_kv, dims.hd
    x = f32(params["embed"])[tokens]
    causal = jnp.tril(jnp.ones((L, L), bool))
    layer0 = params["groups"][0][0]

    def block(x, p):
        p = jax.tree.map(f32, p)
        a, m = p["mixer"], p["ffn"]
        h = _rms(x, p["ln1"]["scale"], dims.eps)
        q, k, v = mm(h, a["wq"]), mm(h, a["wk"]), mm(h, a["wv"])
        if dims.bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(L, H, hd), dims.theta)
        k = _rope(k.reshape(L, K, hd), dims.theta)
        v = v.reshape(L, K, hd)
        G = H // K
        q = q.reshape(L, K, G, hd).transpose(1, 2, 0, 3)        # [K, G, L, hd]
        kt = k.transpose(1, 2, 0)[:, None]                       # [K, 1, hd, L]
        s = mm(q, kt) / jnp.sqrt(jnp.float32(hd))                # [K, G, L, L]
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = mm(w, v.transpose(1, 0, 2)[:, None])                 # [K, G, L, hd]
        o = o.transpose(2, 0, 1, 3).reshape(L, H * hd)
        x = x + mm(o, a["wo"])
        h = _rms(x, p["ln2"]["scale"], dims.eps)
        if dims.act == "silu":
            y = mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]),
                   m["w_down"])
        elif dims.act == "relu2":
            y = mm(jnp.square(jax.nn.relu(mm(h, m["w_in"]))), m["w_out"])
        else:
            raise ValueError(f"hidden_act {dims.act!r} has no reference")
        return x + y, None

    x, _ = jax.lax.scan(block, x, layer0)
    return _rms(x, f32(params["final_norm"]["scale"]), dims.eps)


def head(params, dims: Dims):
    """The output projection [d, vocab] in float32."""
    if dims.tied:
        return params["embed"].astype(jnp.float32).T
    return params["lm_head"].astype(jnp.float32)
