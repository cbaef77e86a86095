"""Paged serving steps: transformer decode over a page-table KV cache.

The dense decode path (train/step.make_decode_step → transformer.forward)
carries [B, K, max_seq, hd] caches per layer. This module is the paged
counterpart: caches live in a physical page pool ([count, P, K, pt, hd] per
layer position, see serve.kvcache.PagedCachePool) and each decode step

  1. computes the new token's K/V per layer,
  2. scatters it into the page mapped at logical position ``lengths[b]``
     (page-table translation, host-filled, device-walked),
  3. attends via the paged flash-decode Pallas kernel
     (kernels/paged_decode_attention.py) with the page table scalar-prefetched.

The group walk mirrors transformer._apply_group — lax.scan over units with
the pattern unrolled inside the body — so HLO stays one-unit-sized regardless
of depth. Only full-attention mixers (gqa/global/shared) are supported;
PagedCachePool rejects anything else at construction.

Per-sequence RoPE positions come from ``lengths`` (each slot rotates at its
own length), which is exact for ragged batches; the dense engine's shared
``cache_pos`` is the max over slots, so the two paths agree whenever slot
lengths coincide (the regression test's request mix).

Tensor parallelism (``tp_axis`` set): the step bodies are written to run
under ``shard_map`` over a ``tp`` mesh axis (serve/executor.py builds the
wrapper under ``parallel.sharding.use_mesh``). KV pages are sharded along
the **kv-head axis** (axis 2 of every [count, P, K, pt, hd] pool leaf);
page tables, lengths, tokens, and all weights stay replicated. Each shard
computes the full QKV projections (replicated math — bit-identical across
shards), slices its own contiguous kv-head block (q heads follow, since
head ``h = k·G + g`` groups query heads per kv head), scatters and attends
only its local page slice, and a single ``all_gather`` of the per-head
partial outputs rebuilds the full head dimension before the (replicated)
output projection. No cross-shard *reduction* ever happens — the gather is
a pure concatenation — so on the CPU tp=N greedy streams are
bit-identical to tp=1 (asserted in tests/test_scheduler_properties.py and
benchmarks/bench_tensor_parallel.py). On TPU v5e chips they differed
(cause not yet traced), and ``chip_smoke.py --tp 2`` holds both to the
float32 reference instead.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import blocks, transformer
from repro.kernels.paged_decode_attention import paged_flash_decode
from repro.kernels.paged_prefill_attention import paged_flash_prefill
from repro.serve import kvquant


def gather_pages(pool: jax.Array, page_ids: jax.Array) -> jax.Array:
    """Pull a sequence's pages out of a pool leaf: [count, P, K, pt, hd] +
    n page ids → [count, n, K, pt, hd]. Dispatched async; the caller chains a
    hero_memcpy_dev2host_async on the result (swap-out's gather phase)."""
    return pool[:, page_ids]


def scatter_pages(pool: jax.Array, rows: jax.Array,
                  page_ids: jax.Array) -> jax.Array:
    """Inverse of gather_pages: land [count, n, K, pt, hd] rows on the given
    page ids of a pool leaf (swap-in's store phase)."""
    return pool.at[:, page_ids].set(rows.astype(pool.dtype))


def copy_page(pool: jax.Array, src: int, dst: int) -> jax.Array:
    """Device-side page duplication — the copy half of copy-on-write. All
    ``page_tokens`` rows of page ``src`` land on page ``dst`` of the same
    pool leaf; the caller (PagedCachePool.cow_unshare) has already moved the
    sequence's page-table entry to ``dst`` via vmm fork_page."""
    return pool.at[:, dst].set(pool[:, src])


def scatter_chunk(pool: jax.Array, rows: jax.Array, page_table: jax.Array,
                  start: jax.Array, page_tokens: int) -> jax.Array:
    """Write a prefill chunk's K/V rows ([C, K, hd]) at logical positions
    ``[start, start+C)`` of one sequence's page list — the chunked-prefill
    counterpart of ``PagedCachePool.write_prefill``, for an *arbitrary* slice
    into already-reserved pages. ``start`` may be a traced scalar (one
    compiled step serves every chunk offset); positions are distinct, so the
    whole chunk lands in one scatter."""
    C = rows.shape[0]
    pos = start + jnp.arange(C, dtype=jnp.int32)
    pids = jnp.maximum(jnp.take(page_table, pos // page_tokens), 0)
    offs = pos % page_tokens
    return pool.at[pids, :, offs].set(rows.astype(pool.dtype))


def scatter_chunk_q(pool: jax.Array, scale: jax.Array, rows: jax.Array,
                    page_table: jax.Array, start: jax.Array,
                    page_tokens: int):
    """Quantized counterpart of :func:`scatter_chunk`: land a prefill
    chunk's f32 K/V rows ([C, K, hd]) in an int8 pool ([P, K, pt, hd]) with
    per-page scales ([P, K]), updating the scales monotonically in the same
    step (serve/kvquant.py): per touched page, ``scale' = max(scale,
    absmax(new rows)/127)``, the page's existing int8 content is rescaled
    by ``scale/scale'``, and the new rows quantize at ``scale'``. A chunk
    covering a whole fresh (zero-scale) page therefore writes bytes
    bit-identical to the host ``write_prefill`` path — both reduce with the
    same shared helpers. Returns (pool', scale').

    Untouched logical pages (and the clamped -1 padding entries) are
    excluded from the page-level writeback via an out-of-bounds index with
    ``mode="drop"`` — they are never read-modify-written, so no two scatter
    indices ever collide."""
    C = rows.shape[0]
    pt = page_tokens
    M = page_table.shape[0]
    pos = start + jnp.arange(C, dtype=jnp.int32)
    lp = pos // pt                                   # logical page per row
    offs = pos % pt
    pids = jnp.maximum(jnp.take(page_table, lp), 0)
    rows_f = rows.astype(jnp.float32)                # [C, K, hd]
    # per-row absmax per kv head, then a segment-max over logical pages
    amax_c = jnp.max(jnp.abs(rows_f), axis=-1)       # [C, K]
    onehot = lp[:, None] == jnp.arange(M, dtype=jnp.int32)[None, :]  # [C, M]
    amax_p = jnp.max(jnp.where(onehot[:, :, None], amax_c[:, None, :], 0.0),
                     axis=0)                         # [M, K]
    touched = jnp.any(onehot, axis=0)                # [M]
    pid_m = jnp.maximum(page_table, 0)               # [M]
    s_old = scale[pid_m]                             # [M, K]
    s_new = jnp.maximum(s_old, amax_p / kvquant.Q_MAX)
    s_new = jnp.where(touched[:, None], s_new, s_old)
    # rescale existing content of touched pages to the widened scale
    repg = kvquant.requantize(pool[pid_m],
                              kvquant.rescale_ratio(s_old, s_new))
    pid_eff = jnp.where(touched, pid_m, pool.shape[0])   # OOB -> dropped
    pool = pool.at[pid_eff].set(repg, mode="drop")
    scale = scale.at[pid_eff].set(s_new, mode="drop")
    # quantize the chunk rows at their page's new scale and scatter them
    q_c = kvquant.quantize(rows_f[:, :, None, :],
                           s_new[lp])[:, :, 0, :]    # [C, K, hd] int8
    return pool.at[pids, :, offs].set(q_c), scale


def _scatter_token(pool: jax.Array, tok: jax.Array, page_table: jax.Array,
                   lengths: jax.Array, active: jax.Array,
                   page_tokens: int) -> jax.Array:
    """Write tok[b] ([B, K, hd]) at logical position lengths[b] of each active
    slot's page list. Inactive slots read-modify-write their target in place
    (a masked no-op), so no trash page is needed."""
    B = tok.shape[0]
    K, hd = tok.shape[1], tok.shape[2]
    for b in range(B):
        pid = jnp.maximum(page_table[b, lengths[b] // page_tokens], 0)
        off = lengths[b] % page_tokens
        val = tok[b].astype(pool.dtype)[None, :, None, :]       # [1, K, 1, hd]
        cur = jax.lax.dynamic_slice(pool, (pid, 0, off, 0), (1, K, 1, hd))
        val = jnp.where(active[b], val, cur)
        pool = jax.lax.dynamic_update_slice(pool, val, (pid, 0, off, 0))
    return pool


def _scatter_token_q(pool: jax.Array, scale: jax.Array, tok: jax.Array,
                     page_table: jax.Array, lengths: jax.Array,
                     active: jax.Array, page_tokens: int):
    """Quantized counterpart of :func:`_scatter_token`: write tok[b]
    ([B, K, hd], f32) at logical position lengths[b] of each active slot's
    int8 page, widening that page's per-head scale monotonically and
    rescaling its existing content in the same step (serve/kvquant.py).
    Inactive slots leave both the page and its scale row bit-untouched —
    the whole page-block update is gated on ``active[b]``, and an active
    write whose scale is unchanged rescales at ratio exactly 1.0 (a
    bit-exact no-op on the already-written rows). Returns (pool', scale')."""
    B = tok.shape[0]
    K, hd = tok.shape[1], tok.shape[2]
    pt = page_tokens
    for b in range(B):
        pid = jnp.maximum(page_table[b, lengths[b] // pt], 0)
        off = lengths[b] % pt
        tok_f = tok[b].astype(jnp.float32)                   # [K, hd]
        s_old = jax.lax.dynamic_slice(scale, (pid, 0), (1, K))[0]
        s_new = jnp.maximum(
            s_old, jnp.max(jnp.abs(tok_f), axis=-1) / kvquant.Q_MAX)
        pg = jax.lax.dynamic_slice(pool, (pid, 0, 0, 0), (1, K, pt, hd))
        repg = kvquant.requantize(
            pg, kvquant.rescale_ratio(s_old, s_new)[None])
        qtok = kvquant.quantize(tok_f[:, None, :], s_new)    # [K, 1, hd]
        upd = jax.lax.dynamic_update_slice(repg, qtok[None], (0, 0, off, 0))
        upd = jnp.where(active[b], upd, pg)
        s_fin = jnp.where(active[b], s_new, s_old)
        pool = jax.lax.dynamic_update_slice(pool, upd, (pid, 0, 0, 0))
        scale = jax.lax.dynamic_update_slice(scale, s_fin[None], (pid, 0))
    return pool, scale


def _tp_head_slice(q, k, v, pages, tp_axis: str):
    """This shard's contiguous head block of replicated q/k/v projections.

    ``pages["k"]`` already carries the *local* kv-head count (shard_map hands
    each shard its pool slice), so the slice sizes are static; only the
    offset (``axis_index``) is traced. q heads follow the kv split because
    head ``h = k·G + g`` lays query heads out kv-head-major."""
    K_local = pages["k"].shape[1]
    G = q.shape[2] // k.shape[2]
    idx = jax.lax.axis_index(tp_axis)
    q = jax.lax.dynamic_slice_in_dim(q, idx * K_local * G, K_local * G, 2)
    k = jax.lax.dynamic_slice_in_dim(k, idx * K_local, K_local, 2)
    v = jax.lax.dynamic_slice_in_dim(v, idx * K_local, K_local, 2)
    return q, k, v


def _paged_gqa_layer(p, x, pages, page_table, lengths, active,
                     cfg: transformer.ModelConfig, acfg, page_tokens: int,
                     interpret: bool, tp_axis=None):
    """One decode-mode attention layer over the paged cache.

    x: [B, 1, d]; pages: {"k","v"} [P, K, pt, hd] (this unit's pool slice —
    the *local* kv-head shard when ``tp_axis`` is set and the caller runs
    under shard_map). Returns (y [B, 1, d], updated pages).
    """
    B = x.shape[0]
    H, K, hd = acfg.n_heads, acfg.n_kv, acfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if acfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, 1, H, hd)
    k = k.reshape(B, 1, K, hd)
    v = v.reshape(B, 1, K, hd)
    if acfg.rope_theta is not None:
        positions = lengths.astype(jnp.int32)[:, None]          # [B, 1]
        q = blocks.apply_rope(q, positions, acfg.rope_theta)
        k = blocks.apply_rope(k, positions, acfg.rope_theta)
    if tp_axis is not None:
        q, k, v = _tp_head_slice(q, k, v, pages, tp_axis)
    # trace-time branch: a quantized pool carries scale leaves, and the
    # pytree structure keys the jit cache — no extra config plumbing needed
    quant = "k_scale" in pages
    if quant:
        k_pool, k_scale = _scatter_token_q(
            pages["k"], pages["k_scale"], k[:, 0], page_table, lengths,
            active, page_tokens)
        v_pool, v_scale = _scatter_token_q(
            pages["v"], pages["v_scale"], v[:, 0], page_table, lengths,
            active, page_tokens)
    else:
        k_pool = _scatter_token(pages["k"], k[:, 0], page_table, lengths,
                                active, page_tokens)
        v_pool = _scatter_token(pages["v"], v[:, 0], page_table, lengths,
                                active, page_tokens)
        k_scale = v_scale = None
    # the freshly written token must be visible: active slots attend over
    # lengths+1 positions
    kv_len = jnp.where(active, lengths + 1, 0).astype(jnp.int32)
    att = paged_flash_decode(q[:, 0].astype(jnp.float32),
                             k_pool, v_pool, page_table, kv_len,
                             k_scale=k_scale, v_scale=v_scale,
                             interpret=interpret)         # [B, H_local, hd]
    if tp_axis is not None:
        # the single tp collective: concatenate per-head partials (each head
        # was computed whole on exactly one shard — no reduction, bit-exact)
        att = jax.lax.all_gather(att, tp_axis, axis=1, tiled=True)
    y = att.reshape(B, 1, H * hd).astype(x.dtype) @ p["wo"]
    out = {"k": k_pool, "v": v_pool}
    if quant:
        out["k_scale"], out["v_scale"] = k_scale, v_scale
    return y, out


def make_paged_decode_step(cfg: transformer.ModelConfig, page_tokens: int,
                           *, interpret: bool, tp_axis=None):
    """Returns decode_step(params, tokens, pages, page_table, lengths, active)
    -> (logits [B, vocab], new pages).

    tokens: [B, 1] int32 (last sampled token per slot); pages: the
    PagedCachePool.pages pytree; page_table: [B, max_pages] int32;
    lengths: [B] int32 valid KV rows (the new token's write position);
    active: [B] bool slot-occupancy mask. ``interpret`` runs the attention
    kernel in the Pallas interpreter (CPU) instead of compiling it (TPU).

    With ``tp_axis`` set, the returned function must be called under
    ``shard_map`` over that mesh axis with pages sharded on their kv-head
    axis and everything else replicated — serve/executor.py owns that
    wrapping (see the module docstring for the layout).
    """

    def decode_step(params, tokens, pages, page_table, lengths, active):
        B = tokens.shape[0]
        cd = cfg.compute_dtype
        lengths = lengths.astype(jnp.int32)
        embed = params["embed"].astype(cd)
        x = blocks.embed_lookup(embed, tokens)                  # [B, 1, d]
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)

        shared_p = transformer._cast(params.get("shared_block"), cd)
        new_pages = []
        for gi, (pattern, count) in enumerate(cfg.groups):
            gp = params["groups"][gi]
            gpg = pages[gi]

            def unit_body(x, xs, pattern=pattern):
                unit_p, unit_pg = xs
                unit_p = transformer._barrier(unit_p)
                unit_p = transformer._cast(unit_p, cd)
                new_pgs = []
                for i, kind in enumerate(pattern):
                    mixer, ffn = transformer.parse_kind(kind)
                    p = unit_p[i]
                    h = transformer._norm_apply(p["ln1"], x, cfg)
                    mixer_p = shared_p["mixer"] if mixer == "shared" else p["mixer"]
                    y, npg = _paged_gqa_layer(
                        mixer_p, h, unit_pg[i], page_table, lengths, active,
                        cfg, cfg.attn_cfg(mixer), page_tokens, interpret,
                        tp_axis)
                    if cfg.sandwich_norm:
                        y = transformer._norm_apply(p["ln1_post"], y, cfg)
                    x = x + y
                    if ffn != "none":
                        h2 = transformer._norm_apply(p["ln2"], x, cfg)
                        ffn_p = shared_p["ffn"] if mixer == "shared" else p["ffn"]
                        y2, _ = transformer._ffn_apply(ffn_p, ffn, h2, cfg)
                        if cfg.sandwich_norm:
                            y2 = transformer._norm_apply(p["ln2_post"], y2, cfg)
                        x = x + y2
                    new_pgs.append(npg)
                return x, tuple(new_pgs)

            x, ngp = jax.lax.scan(unit_body, x, (gp, gpg))
            new_pages.append(ngp)

        h_final = transformer._norm_apply(
            transformer._cast(params["final_norm"], cd), x, cfg)
        head = (embed.T if cfg.tie_embeddings else params["lm_head"].astype(cd))
        logits = h_final @ head                                  # [B, 1, vocab]
        return logits[:, 0], new_pages

    return decode_step


def _paged_gqa_prefill_layer(p, x, pages, page_table, start,
                             cfg: transformer.ModelConfig, acfg,
                             page_tokens: int, interpret: bool, tp_axis=None):
    """One prefill-chunk attention layer over the paged cache.

    x: [1, C, d] chunk hidden states at global positions start..start+C-1;
    pages: {"k","v"} [P, K, pt, hd] (this unit's pool slice — the local
    kv-head shard under ``tp_axis``); page_table: [max_pages] (one
    sequence's row). Writes the chunk's K/V into its pages, then attends
    the chunk queries against the paged prefix with the cross-chunk causal
    mask. Returns (y [1, C, d], updated pages).
    """
    C = x.shape[1]
    H, K, hd = acfg.n_heads, acfg.n_kv, acfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if acfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(1, C, H, hd)
    k = k.reshape(1, C, K, hd)
    v = v.reshape(1, C, K, hd)
    if acfg.rope_theta is not None:
        positions = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
        q = blocks.apply_rope(q, positions, acfg.rope_theta)
        k = blocks.apply_rope(k, positions, acfg.rope_theta)
    if tp_axis is not None:
        q, k, v = _tp_head_slice(q, k, v, pages, tp_axis)
    quant = "k_scale" in pages
    if quant:
        k_pool, k_scale = scatter_chunk_q(
            pages["k"], pages["k_scale"], k[0], page_table, start,
            page_tokens)
        v_pool, v_scale = scatter_chunk_q(
            pages["v"], pages["v_scale"], v[0], page_table, start,
            page_tokens)
    else:
        k_pool = scatter_chunk(pages["k"], k[0], page_table, start,
                               page_tokens)
        v_pool = scatter_chunk(pages["v"], v[0], page_table, start,
                               page_tokens)
        k_scale = v_scale = None
    att = paged_flash_prefill(q[0].astype(jnp.float32), k_pool, v_pool,
                              page_table, start,
                              k_scale=k_scale, v_scale=v_scale,
                              interpret=interpret)         # [C, H_local, hd]
    if tp_axis is not None:
        att = jax.lax.all_gather(att, tp_axis, axis=1, tiled=True)
    y = att.reshape(1, C, H * hd).astype(x.dtype) @ p["wo"]
    out = {"k": k_pool, "v": v_pool}
    if quant:
        out["k_scale"], out["v_scale"] = k_scale, v_scale
    return y, out


def make_paged_prefill_chunk_step(cfg: transformer.ModelConfig,
                                  page_tokens: int, *, interpret: bool,
                                  tp_axis=None):
    """Returns prefill_chunk(params, tokens, pages, page_table, start)
    -> (last_logits [1, vocab], new pages) — the chunked-prefill TargetRegion.

    tokens: [1, C] int32 prompt slice ``prompt[start:start+C]``; pages: the
    PagedCachePool.pages pytree; page_table: [max_pages] int32 (the owning
    sequence's row, every page covering the *prompt* already reserved at
    admission); start: scalar int32 chunk offset — traced, so one compile
    serves every offset of a given chunk size. The returned logits are the
    chunk's last position; the engine samples from them only when the chunk
    completes the prompt. ``interpret`` as in :func:`make_paged_decode_step`.
    """

    def prefill_chunk(params, tokens, pages, page_table, start):
        cd = cfg.compute_dtype
        start = start.astype(jnp.int32)
        embed = params["embed"].astype(cd)
        x = blocks.embed_lookup(embed, tokens)                  # [1, C, d]
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cd)

        shared_p = transformer._cast(params.get("shared_block"), cd)
        new_pages = []
        for gi, (pattern, count) in enumerate(cfg.groups):
            gp = params["groups"][gi]
            gpg = pages[gi]

            def unit_body(x, xs, pattern=pattern):
                unit_p, unit_pg = xs
                unit_p = transformer._barrier(unit_p)
                unit_p = transformer._cast(unit_p, cd)
                new_pgs = []
                for i, kind in enumerate(pattern):
                    mixer, ffn = transformer.parse_kind(kind)
                    p = unit_p[i]
                    h = transformer._norm_apply(p["ln1"], x, cfg)
                    mixer_p = shared_p["mixer"] if mixer == "shared" else p["mixer"]
                    y, npg = _paged_gqa_prefill_layer(
                        mixer_p, h, unit_pg[i], page_table, start,
                        cfg, cfg.attn_cfg(mixer), page_tokens, interpret,
                        tp_axis)
                    if cfg.sandwich_norm:
                        y = transformer._norm_apply(p["ln1_post"], y, cfg)
                    x = x + y
                    if ffn != "none":
                        h2 = transformer._norm_apply(p["ln2"], x, cfg)
                        ffn_p = shared_p["ffn"] if mixer == "shared" else p["ffn"]
                        y2, _ = transformer._ffn_apply(ffn_p, ffn, h2, cfg)
                        if cfg.sandwich_norm:
                            y2 = transformer._norm_apply(p["ln2_post"], y2, cfg)
                        x = x + y2
                    new_pgs.append(npg)
                return x, tuple(new_pgs)

            x, ngp = jax.lax.scan(unit_body, x, (gp, gpg))
            new_pages.append(ngp)

        h_final = transformer._norm_apply(
            transformer._cast(params["final_norm"], cd), x, cfg)
        head = (embed.T if cfg.tie_embeddings else params["lm_head"].astype(cd))
        logits = h_final @ head                                  # [1, C, vocab]
        return logits[:, -1], new_pages

    return prefill_chunk
