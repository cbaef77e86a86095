"""Paged flash-decode Pallas kernel: page-table KV gather + online softmax.

HEROv2's SVM insight (§2.2) applied to the serving hot loop: the KV cache is
a pool of fixed-size *physical pages* ([n_pages, K, page_tokens, hd]) and each
sequence owns an ordered *page list*. The device-side page table (int32 rows,
per the addrspace promotion analysis — page *ids* stay native 32-bit even when
page *byte offsets* exceed 2³¹) translates logical token position → physical
page, exactly like the paper's IOMMU translates accelerator-virtual → host-
physical addresses.

Kernel structure: grid (B, n_blocks). Each grid step handles one *block* of
``ppb`` whole pages of one slot (:func:`pages_per_block`: at least 128
positions where the table is that long) for **all** kv heads at once, each
head's GQA group computed in the same step; (m, l, acc) online-softmax
scratch is carried across a slot's blocks. A block arrives as ``ppb`` page
operands per pool, column j holding page ``i·ppb + j`` of the slot: one copy
per page moves its [K, pt, hd] slab, contiguous in the pool, and Pallas's
pipeline double-buffers every column, so the next step's pages — the slot's
next block, or across a slot boundary the next slot's first — are in flight
while a block computes. The page indirection happens in the index maps via
**scalar prefetch**: the walk's table is prefetched to SMEM before the body
runs, so the DMA engine fetches each page straight from the pool. The pages
are copied by the pipeline, not by DMAs the body starts itself: Mosaic
refuses a DMA out of a pool whose head dim is under 128 lanes (qwen2-0.5b's
64), and at head dim 128 the pipelined walk ran as fast on a v5e as
hand-started, double-buffered page copies.

Nothing past a slot's length is fetched. The pipeline copies a column only
when its page id differs from the one it held the step before, so the walk's
table (:func:`walk_table`, same shape as the page table) is the page table
with every entry past its slot's length replaced by the page its column last
held (the next one it will hold, before any): a block past the length copies
nothing and does no math, and pages of the last live block past the length
are not copied. Their score columns are masked and their V rows zeroed (the
buffer holds an earlier page, and 0 × NaN is NaN). Slots of length 0 return
zeros. The last block may be ragged: ``ppb`` need not divide ``max_pages``,
and a column past the table repeats the page it held in the block before.

Numerics: f32 q, bf16 (or int8 × scale) K/V upcast to f32 in VMEM, f32
online softmax and accumulation.

Validated in interpret mode against ref.decode_attention over ragged lengths,
GQA group counts, page sizes, block edges and planted NaN pages
(tests/test_kernels.py); compiled for a described v5e in
tests/test_tpu_compile.py.

Tensor parallelism: the kernel is **head-slice clean** — no computation
crosses kv heads, so the serving executor (serve/executor.py) calls it under
``shard_map`` with ``k_pages``/``v_pages`` holding only the shard's kv-head
slice and ``q`` the matching query-head block (head h = k·G + g is
kv-head-major). The page table and lengths stay replicated; per-head outputs
are exact regardless of how heads are split, so tp=N results concatenate
bit-identically to tp=1.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref

NEG = -1e30
BLOCK_TOKENS = 128            # positions a block covers at least
BLOCK_VMEM = 4 * 1024 * 1024  # most bytes the double-buffered K/V blocks take


def pages_per_block(page_tokens: int, max_pages: int, page_bytes: int) -> int:
    """Pages each grid step covers: enough for ``BLOCK_TOKENS`` positions,
    no more than the table holds, and few enough that two K and two V
    blocks of ``page_bytes`` pages (all kv heads) fit ``BLOCK_VMEM``."""
    ppb = min(max_pages, pl.cdiv(BLOCK_TOKENS, page_tokens))
    return max(1, min(ppb, BLOCK_VMEM // (4 * page_bytes)))


def walk_table(page_table: jax.Array, lengths: jax.Array, n_pages: int,
               page_tokens: int, ppb: int) -> jax.Array:
    """The page table the kernel walks: [B, max_pages] page ids in
    ``[0, n_pages)``, where each entry past its slot's length holds the page
    its block column (entry index mod ``ppb``) held last in the walk's order
    — slot by slot, block by block — or, before any, the first it will
    hold. The pipeline then copies nothing for it."""
    B, max_pages = page_table.shape
    n_blocks = pl.cdiv(max_pages, ppb)
    pad = n_blocks * ppb - max_pages
    pos = jnp.arange(n_blocks * ppb, dtype=jnp.int32) * page_tokens
    live = (pos[None, :] < lengths[:, None]).reshape(B * n_blocks, ppb)
    table = jnp.pad(page_table, ((0, 0), (0, pad))).reshape(B * n_blocks, ppb)
    step = jnp.arange(B * n_blocks, dtype=jnp.int32)[:, None]
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    first = jax.lax.cummin(jnp.where(live, step, B * n_blocks - 1), axis=0,
                           reverse=True)
    src = jnp.where(last >= 0, last, first)
    held = jnp.take_along_axis(table, src, axis=0).reshape(B, -1)
    return jnp.clip(held[:, :max_pages], 0, n_pages - 1)


def paged_flash_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       page_table: jax.Array, lengths: jax.Array,
                       k_scale=None, v_scale=None, *,
                       interpret: bool) -> jax.Array:
    """One-token attention over a paged KV cache.

    q:          [B, H, hd]
    k_pages:    [P, K, pt, hd] physical page pool (P pages of pt tokens)
    v_pages:    [P, K, pt, hd]
    page_table: [B, max_pages] int32 page ids, -1 = unmapped
    lengths:    [B] int32 valid token counts (0 = inactive slot)
    k_scale:    optional [P, K] f32 per-(page, kv-head) dequant scales for
                an int8 pool (serve/kvquant.py); each page dequantizes
                **in VMEM** — int8 rows × scale → f32 — before the f32
                softmax accumulation. A page's scale row rides the same
                walk as its page (its BlockSpec index map is the same table
                lookup), one K-wide row per page covering every head.
    v_scale:    optional [P, K] f32 (must accompany ``k_scale``)
    interpret:  run the Pallas body in the interpreter (CPU) instead of
                compiling it with Mosaic (TPU).
    Returns [B, H, hd].
    """
    B, H, hd = q.shape
    P, K, pt, _ = k_pages.shape
    G = H // K
    max_pages = page_table.shape[1]
    scale = 1.0 / math.sqrt(hd)
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("paged_flash_decode: k_scale and v_scale must be "
                         "given together")
    ppb = pages_per_block(pt, max_pages, K * pt * hd * k_pages.dtype.itemsize)
    n_blocks = pl.cdiv(max_pages, ppb)
    T = ppb * pt                                   # positions in a block
    lengths = lengths.astype(jnp.int32)
    table = walk_table(page_table.astype(jnp.int32), lengths, P, pt, ppb)

    n_in = 4 if quant else 2            # page operands per column: K, V(, scales)

    def kernel(tbl_ref, len_ref, q_ref, *refs):
        k_refs, v_refs, *scale_refs = [refs[n * ppb:(n + 1) * ppb]
                                       for n in range(n_in)]
        o_ref, m_ref, l_ref, acc_ref = refs[n_in * ppb:]
        ks_refs, vs_refs = scale_refs or (None, None)
        b, i = pl.program_id(0), pl.program_id(1)
        length = len_ref[b]

        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def rows(pages, scales, h):
            # head h's [T, hd] f32 rows of the block, page by page
            out = [r[0, h].astype(jnp.float32) for r in pages]
            if quant:
                # dequantize in VMEM: int8 rows × their page's scale
                out = [x * head_scale(s, h, pt) for x, s in zip(out, scales)]
            return jnp.concatenate(out, axis=0)

        @pl.when(i * T < length)
        def _block():
            kpos = i * T + jax.lax.broadcasted_iota(jnp.int32, (G, T), 1)
            vpos = i * T + jax.lax.broadcasted_iota(jnp.int32, (T, hd), 0)
            for h in range(K):
                qh = q_ref[0, h].astype(jnp.float32)                 # [G, hd]
                kh = rows(k_refs, ks_refs, h)
                vh = jnp.where(vpos < length, rows(v_refs, vs_refs, h), 0.0)
                s = jnp.dot(qh, kh.T, preferred_element_type=jnp.float32)
                s = jnp.where(kpos < length, s * scale, NEG)
                m_prev = m_ref[h]                                    # [G, 1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[h] = acc_ref[h] * corr + \
                    jnp.dot(p, vh, preferred_element_type=jnp.float32)
                m_ref[h] = m_new

        @pl.when(i == n_blocks - 1)
        def _fin():
            o_ref[0] = (acc_ref[...] /
                        jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    def page_spec(j, block):
        def index(b, i, tbl, lens):
            # column j of block i; past the table (a ragged last block), the
            # page column j held in block i - 1, so nothing is copied
            g = i * ppb + j
            return (tbl[b, jnp.where(g < max_pages, g, g - ppb)],) + \
                (0,) * (len(block) - 1)
        return pl.BlockSpec(block, index)

    slot_spec = pl.BlockSpec((1, K, G, hd), lambda b, i, tbl, lens: (b, 0, 0, 0))
    in_specs = [slot_spec] + [page_spec(j, (1, K, pt, hd))
                              for j in range(ppb)] * 2
    inputs = [table, lengths, q.reshape(B, K, G, hd)] + \
        [k_pages] * ppb + [v_pages] * ppb
    if quant:
        # scale rows walk the same table as their pages, in a [P, 1, K]
        # view (see head_scale)
        in_specs += [page_spec(j, (1, 1, K)) for j in range(ppb)] * 2
        inputs += [k_scale.reshape(P, 1, K)] * ppb + \
            [v_scale.reshape(P, 1, K)] * ppb

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # the walk's table, lengths
        grid=(B, n_blocks),
        in_specs=in_specs,
        out_specs=slot_spec,
        scratch_shapes=[pltpu.VMEM((K, G, 1), jnp.float32),
                        pltpu.VMEM((K, G, 1), jnp.float32),
                        pltpu.VMEM((K, G, hd), jnp.float32)],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(*inputs)
    return out.reshape(B, H, hd)


def head_scale(scale_ref, kv_head, rows: int) -> jax.Array:
    """Pick ``kv_head``'s scale out of a page's ``(1, 1, K)`` scale block as
    a ``(rows, 1)`` column, ready to scale a ``(rows, hd)`` page block.

    The kernels see the ``[P, K]`` scales as ``[P, 1, K]``: Mosaic tiles the
    last two block dims by (8, 128) unless they span the whole array, so a
    ``(1, 1)`` block over ``[P, K]`` cannot lower, while ``(1, 1, K)`` spans
    both. The pick is a masked lane sum, since Mosaic has no dynamic lane
    load; the row is broadcast over sublanes first, since Mosaic cannot
    broadcast a ``(1, 1)`` value over sublanes and lanes at once."""
    row = jnp.broadcast_to(scale_ref[0], (rows, scale_ref.shape[-1]))
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == kv_head, row, 0.0), axis=1,
                   keepdims=True)


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize a dense [B, K, max_pages·pt, hd] cache from the page pool
    (test oracle + debugging; the kernel never does this)."""
    B, max_pages = page_table.shape
    _, K, pt, hd = pages.shape
    dense = jnp.take(pages, jnp.maximum(page_table, 0).reshape(-1), axis=0)
    dense = dense.reshape(B, max_pages, K, pt, hd)
    return jnp.transpose(dense, (0, 2, 1, 3, 4)).reshape(B, K, max_pages * pt, hd)


def dequant_pages(pages: jax.Array, page_scale: jax.Array) -> jax.Array:
    """Dequantize an int8 page pool dense: [P, K, pt, hd] × [P, K] → f32
    (test oracle + debugging; the kernel dequantizes per block in VMEM)."""
    return pages.astype(jnp.float32) * page_scale[:, :, None, None]


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, lengths,
                               k_scale=None, v_scale=None):
    """Oracle: gather pages dense (dequantizing first when scales are
    given), then the masked-softmax decode oracle."""
    if k_scale is not None:
        k_pages = dequant_pages(k_pages, k_scale)
        v_pages = dequant_pages(v_pages, v_scale)
    k_dense = gather_pages(k_pages, page_table)
    v_dense = gather_pages(v_pages, page_table)
    return ref.decode_attention(q, k_dense, v_dense, lengths)
