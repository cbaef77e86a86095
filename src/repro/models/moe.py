"""Mixture-of-Experts: top-k router + capacity-based dispatch, EP-shardable.

Two assigned MoE archs exercise two sharding regimes:
  * deepseek-v3: 256 routed experts + 1 shared — experts sharded over the
    16-way ``model`` axis (EP, 16 experts/device). Activations are replicated
    over ``model`` between blocks (our TP layout), so dispatch needs NO
    all-to-all: each model-rank gathers the tokens routed to *its* experts
    locally and the combine is the same psum a row-parallel matmul needs.
    (The a2a dispatch variant is a hillclimb lever; see EXPERIMENTS §Perf.)
  * granite-moe: 40 experts (∤16) — experts stay replicated over ``model``
    and shard over ``data`` (FSDP) instead; sharding.py drops the non-dividing
    binding automatically.

Dispatch is capacity-based (GShard/Switch lineage): per-expert top-C token
selection keeps shapes static (XLA-friendly, differentiable); capacity_factor
1.25 bounds dropping. FLOPs ≈ active-expert FLOPs × cf — the useful-flops
ratio the roofline §Perf tracks. Router: softmax top-k (granite) or
sigmoid+renorm (deepseek-v3) with an optional switch-style aux loss.

Mixed-data-model note (HEROv2 §2.2.1): dispatch indices are (expert, slot)
pairs — never flattened token·expert offsets, which would exceed int32 at
1M-token × 256-expert scale; addrspace.index_dtype guards the invariant.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import addrspace
from repro.models import blocks
from repro.models.blocks import Param, dense_init
from repro.parallel.sharding import constrain


def _pmean_grad_safe(x, axes):
    """pmean whose VJP materializes symbolic-Zero cotangents.

    Differentiating only the token output (ignoring aux) hands pmean a
    Zero cotangent, which this jax version's psum transpose rejects
    ("Zero ... is not a valid JAX type"). custom_vjp instantiates the zero
    before our bwd runs; for the replicated scalars this is used on, the
    cotangent is itself replicated, so pmean is its own adjoint here.
    """
    @jax.custom_vjp
    def f(x):
        return jax.lax.pmean(x, axes)

    def fwd(x):
        return f(x), None

    def bwd(_, g):
        return (jax.lax.pmean(g, axes),)

    f.defvjp(fwd, bwd)
    return f(x)


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    d_model: int
    n_experts: int
    top_k: int
    d_ff: int                    # per-expert hidden
    n_shared: int = 0            # shared experts (deepseek: 1)
    router: str = "softmax"      # "softmax" | "sigmoid" (deepseek-v3)
    capacity_factor: float = 1.25
    aux_weight: float = 0.01
    ep: bool = True              # expert-parallel over 'model' (if divisible)
    dispatch: str = "gather"     # "gather" (psum-EP) | "a2a" (deepseek-style)


def init_moe(key, cfg: MoeConfig, dtype=jnp.float32) -> Dict[str, Param]:
    ks = jax.random.split(key, 5)
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff
    expert_axes = ("expert", "embed_fsdp", None) if cfg.ep else (None, "embed_fsdp", "mlp_tp")
    expert_axes_out = ("expert", None, "embed_fsdp") if cfg.ep else (None, "mlp_tp", "embed_fsdp")
    p = {
        "router": dense_init(ks[0], (d, E), ("embed_fsdp", None), jnp.float32),
        "w_gate": dense_init(ks[1], (E, d, f), expert_axes, dtype),
        "w_up": dense_init(ks[2], (E, d, f), expert_axes, dtype),
        "w_down": dense_init(ks[3], (E, f, d), expert_axes_out, dtype),
    }
    if cfg.n_shared:
        sk = jax.random.split(ks[4], 3)
        fs = cfg.d_ff * cfg.n_shared
        p["shared"] = {
            "w_gate": dense_init(sk[0], (d, fs), ("embed_fsdp", "mlp_tp"), dtype),
            "w_up": dense_init(sk[1], (d, fs), ("embed_fsdp", "mlp_tp"), dtype),
            "w_down": dense_init(sk[2], (fs, d), ("mlp_tp", "embed_fsdp"), dtype),
        }
    return p


def route(router_w: jax.Array, x_flat: jax.Array, cfg: MoeConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x_flat: [N, d] -> (gates [N,k], expert_idx [N,k] int32, aux_loss)."""
    logits = x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [N,E]
    if cfg.router == "sigmoid":  # deepseek-v3: sigmoid scores, renorm top-k
        scores = jax.nn.sigmoid(logits)
        gates, idx = jax.lax.top_k(scores, cfg.top_k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg.top_k)
    # switch-style load-balance aux: E * Σ_e fraction_e · mean_prob_e
    probs_full = jax.nn.softmax(logits, axis=-1)
    me = probs_full.mean(0)
    one_hot = jax.nn.one_hot(idx[:, 0], cfg.n_experts, dtype=jnp.float32)
    ce = one_hot.mean(0)
    aux = cfg.n_experts * jnp.sum(me * ce)
    # expert ids are NATIVE32 by construction (E < 2^31) — addrspace check:
    assert addrspace.index_dtype((cfg.n_experts,)) == jnp.int32
    return gates.astype(x_flat.dtype), idx.astype(jnp.int32), aux


def capacity(n_tokens: int, cfg: MoeConfig) -> int:
    c = int(math.ceil(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return min(n_tokens, max(8, -(-c // 8) * 8))  # sublane-aligned, ≤ N (decode)


def _dispatch_compute(xf, router_w, w_gate, w_up, w_down, cfg: MoeConfig,
                      e_lo, e_n: int, slot_rank, n_slots: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Local capacity dispatch over xf:[N_l, d].

    This rank computes experts [e_lo, e_lo+e_n) (EP) over capacity-slot
    slice ``slot_rank`` of ``n_slots`` (slot-parallel when experts don't
    divide the model axis). ``e_lo``/``slot_rank`` may be traced
    (axis_index); the slice SIZES are static. Routing is computed locally
    (tokens are replicated across the model axis in our TP layout →
    identical results on every rank; no dispatch all-to-all — the combine
    psum is the only collective, same cost as a row-parallel matmul).
    """
    N = xf.shape[0]
    d = xf.shape[1]
    gates, idx, aux = route(router_w, xf, cfg)
    E, C = cfg.n_experts, capacity(N, cfg)
    C_l = -(-C // n_slots)
    C_pad = C_l * n_slots
    Np = max(N, C_pad)                       # top_k needs k ≤ axis size
    gate_mat = jnp.zeros((Np, E), jnp.float32)
    gate_mat = gate_mat.at[jnp.arange(N)[:, None], idx].set(gates.astype(jnp.float32))
    # per-expert top-C token selection (static shapes)
    sel_gates, sel_tok = jax.lax.top_k(gate_mat.T, C_pad)    # [E, C_pad]
    # this rank's slice of the (expert, slot) work grid
    sel_gates = jax.lax.dynamic_slice(sel_gates, (e_lo, slot_rank * C_l),
                                      (e_n, C_l))
    sel_tok = jax.lax.dynamic_slice(sel_tok, (e_lo, slot_rank * C_l),
                                    (e_n, C_l))
    sel_valid = sel_gates > 0.0
    sel_tok = jnp.where(sel_valid, jnp.minimum(sel_tok, N - 1), 0)

    xg = xf[sel_tok]                                          # [e_n, C_l, d]
    xg = jnp.where(sel_valid[..., None], xg, 0.0)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w_gate)) * \
        jnp.einsum("ecd,edf->ecf", xg, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down)                # [e_n, C_l, d]
    ye = ye * sel_gates[..., None].astype(ye.dtype)
    y = jnp.zeros((N, d), ye.dtype).at[sel_tok.reshape(-1)].add(
        ye.reshape(-1, d), mode="drop")
    return y, aux


def moe_forward(p: Dict[str, jax.Array], x: jax.Array, cfg: MoeConfig
                ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, L, d] -> (y, aux_loss). shard_map capacity dispatch:
    per-data-shard routing/capacity; model axis splits experts (EP) or
    capacity slots (40∤16 granite); combine = psum over 'model'."""
    from repro.parallel import sharding as shlib
    from jax.sharding import PartitionSpec as P

    B, L, d = x.shape
    mesh = shlib.current_mesh()
    E = cfg.n_experts
    use_map = (mesh is not None and "model" in mesh.shape
               and B % (_batch_shards(mesh) or 1) == 0)

    if not use_map:
        xf = x.reshape(B * L, d)
        y, aux = _dispatch_compute(xf, p["router"], p["w_gate"], p["w_up"],
                                   p["w_down"], cfg, 0, E, 0, 1)
    else:
        M = mesh.shape["model"]
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        ep = cfg.ep and E % M == 0

        use_a2a = (cfg.dispatch == "a2a" and ep and L % M == 0)

        def local(xb, rw, wg, wu, wd):
            # xb: [B_l, L, d]; expert weights: local slice if ep else full
            r = jax.lax.axis_index("model")
            xf = xb.reshape(-1, d)
            if ep:
                y, aux = _dispatch_compute(xf, rw, wg, wu, wd, cfg,
                                           r * (E // M), E // M, 0, 1)
            else:
                y, aux = _dispatch_compute(xf, rw, wg, wu, wd, cfg,
                                           0, E, r, M)
            y = jax.lax.psum(y, "model")
            # aux comes from routing on model-replicated tokens → already
            # invariant over 'model'; mean over the batch axes makes the
            # scalar fully replicated (P() out_spec)
            aux = _pmean_grad_safe(aux, batch_axes)
            return y.reshape(xb.shape), aux

        def local_a2a(xb, rw, wg, wu, wd):
            """DeepSeek-style EP: tokens seq-split over 'model', two
            all-to-alls route (token, gate) to the owning expert rank and
            back. Collective volume per layer ≈ 2·topk·cf·N/M·d vs the
            gather path's psum of N·d — the win grows with M (EXPERIMENTS
            §Perf discusses the crossover)."""
            xl = xb.reshape(-1, d)                      # [N_l, d], N_l = B_l·L/M
            N_l = xl.shape[0]
            gates, idx, aux = route(rw, xl, cfg)
            C = capacity(N_l, cfg)
            Em = E // M                                  # experts per rank
            gate_mat = jnp.zeros((max(N_l, C), E), jnp.float32)
            gate_mat = gate_mat.at[jnp.arange(N_l)[:, None], idx].set(
                gates.astype(jnp.float32))
            sel_g, sel_t = jax.lax.top_k(gate_mat.T, C)  # [E, C]
            sel_valid = sel_g > 0.0
            sel_t = jnp.where(sel_valid, jnp.minimum(sel_t, N_l - 1), 0)
            xsend = xl[sel_t.reshape(E * C)].reshape(M, Em * C, d)
            xsend = jnp.where(sel_valid.reshape(M, Em * C)[..., None], xsend, 0.0)
            # a2a #1: dispatch tokens to expert owners → [M, Em·C, d]
            xrecv = jax.lax.all_to_all(xsend, "model", split_axis=0,
                                       concat_axis=0, tiled=True)
            xg = xrecv.reshape(M, Em, C, d).transpose(1, 0, 2, 3) \
                      .reshape(Em, M * C, d)
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, wg)) * \
                jnp.einsum("ecd,edf->ecf", xg, wu)
            ye = jnp.einsum("ecf,efd->ecd", h, wd)        # [Em, M·C, d]
            ysend = ye.reshape(Em, M, C, d).transpose(1, 0, 2, 3) \
                      .reshape(M, Em * C, d)
            # a2a #2: combine back to token owners
            yrecv = jax.lax.all_to_all(ysend, "model", split_axis=0,
                                       concat_axis=0, tiled=True)
            yrecv = yrecv.reshape(E, C, d) * sel_g[..., None].astype(yrecv.dtype)
            y = jnp.zeros((N_l, d), yrecv.dtype).at[sel_t.reshape(-1)].add(
                yrecv.reshape(E * C, d), mode="drop")
            aux = _pmean_grad_safe(aux, ("model",) + batch_axes)
            return y.reshape(xb.shape), aux

        wspec = P("model", None, None) if ep else P(None, None, None)
        if use_a2a:  # tokens seq-split over model for the dispatch region
            xspec = P(batch_axes if batch_axes else None, "model", None)
            fn = local_a2a
        else:
            xspec = P(batch_axes if batch_axes else None, None, None)
            fn = local
        y, aux = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(xspec, P(None, None), wspec, wspec, wspec),
            out_specs=(xspec, P()),
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
        y = y.reshape(B * L, d)
        aux = aux if aux.ndim == 0 else aux[()]

    xf = x.reshape(B * L, d)
    if cfg.n_shared:
        y = y + blocks.swiglu(p["shared"]["w_gate"], p["shared"]["w_up"],
                              p["shared"]["w_down"], xf)
    y = y.reshape(B, L, d)
    return constrain(y, "batch", None, None), aux * cfg.aux_weight


def _batch_shards(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            n *= mesh.shape[a]
    return n
