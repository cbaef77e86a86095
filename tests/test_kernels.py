"""Per-kernel allclose vs ref.py oracles, swept over shapes/dtypes/modes
(interpret=True executes the Pallas bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autodma
from repro.kernels import flash_attention as fa
from repro.kernels import gemm as gemm_mod
from repro.kernels import polybench as pb
from repro.kernels import ref

RNG = np.random.default_rng(0)
BUDGET = 512 * 1024  # small VMEM budget → real multi-block grids at test sizes


def rand(*shape, dtype=np.float32):
    return RNG.standard_normal(shape).astype(dtype)


@pytest.mark.parametrize("M,N,K", [(128, 128, 128), (256, 384, 512),
                                   (8, 128, 256), (136, 128, 128)])
@pytest.mark.parametrize("mode", ["autodma", "paper", "unmodified"])
def test_gemm_modes(M, N, K, mode):
    A, B = rand(M, K), rand(K, N)
    out, plan = gemm_mod.gemm(A, B, mode=mode, budget=BUDGET)
    np.testing.assert_allclose(np.asarray(out), ref.gemm(A, B), rtol=2e-4,
                               atol=2e-4)
    assert plan.vmem_bytes <= BUDGET or mode == "unmodified"


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_gemm_dtypes(dtype):
    A = jnp.asarray(rand(128, 256), dtype)
    B = jnp.asarray(rand(256, 128), dtype)
    out, _ = gemm_mod.gemm(A, B, budget=BUDGET)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.gemm(A, B), np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("body", ["mxu", "vpu", "loop"])
def test_gemm_isa_bodies(body):
    A, B = rand(128, 256), rand(256, 128)
    out, _ = gemm_mod.gemm(A, B, body=body, budget=BUDGET)
    np.testing.assert_allclose(np.asarray(out), ref.gemm(A, B), rtol=2e-4,
                               atol=2e-4)


def test_gemm_handwritten():
    A, B = rand(256, 256), rand(256, 256)
    out, plan = gemm_mod.gemm(A, B, handwritten_tiles=(128, 128, 256))
    assert plan.mode == "handwritten"
    np.testing.assert_allclose(np.asarray(out), ref.gemm(A, B), rtol=2e-4,
                               atol=2e-4)


def test_2mm_3mm():
    A, B, C, D = rand(64, 128), rand(128, 256), rand(256, 128), rand(128, 64)
    out, _ = pb.mm2(A, B, C, budget=BUDGET)
    np.testing.assert_allclose(np.asarray(out), ref.mm2(A, B, C), rtol=2e-3,
                               atol=2e-3)
    out3, _ = pb.mm3(A, B, C, D, budget=BUDGET)
    np.testing.assert_allclose(np.asarray(out3), ref.mm3(A, B, C, D),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("M,N", [(256, 256), (512, 384)])
def test_atax_bicg(M, N):
    A, x = rand(M, N), rand(N)
    y, _ = pb.atax(A, x, budget=BUDGET)
    np.testing.assert_allclose(np.asarray(y), ref.atax(A, x), rtol=2e-3,
                               atol=2e-3)
    p, r = rand(N), rand(M)
    (q, s), _ = pb.bicg(A, p, r, budget=BUDGET)
    qr, sr = ref.bicg(A, p, r)
    np.testing.assert_allclose(np.asarray(q), qr, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(s), sr, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("H,W", [(64, 128), (128, 256), (96, 128)])
def test_conv2d(H, W):
    A = rand(H, W)
    c = rand(3, 3)
    out, _ = pb.conv2d(A, c, budget=BUDGET, row_tile=32)
    np.testing.assert_allclose(np.asarray(out), ref.conv2d(A, c), rtol=2e-4,
                               atol=2e-4)


def test_covar():
    D = rand(256, 128)
    out, _ = pb.covar(D, budget=BUDGET)
    np.testing.assert_allclose(np.asarray(out), ref.covar(D), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("B,H,L,hd", [(1, 2, 256, 64), (2, 4, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_pallas(B, H, L, hd, causal):
    q, k, v = rand(B, H, L, hd), rand(B, H, L, hd), rand(B, H, L, hd)
    out = fa.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal=causal, block_q=64, block_k=64)
    exp = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_window():
    B, H, L, hd = 1, 2, 256, 64
    q, k, v = rand(B, H, L, hd), rand(B, H, L, hd), rand(B, H, L, hd)
    out = fa.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal=True, window=64, block_q=64, block_k=64)
    exp = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                        causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


def test_flash_xla_matches_ref_and_grad():
    """The XLA custom-VJP flash (models/flash_xla) vs oracle + numeric grad."""
    from repro.models.flash_xla import flash_attention_xla
    B, H, L, hd = 1, 2, 128, 32
    q, k, v = (jnp.asarray(rand(B, H, L, hd)) for _ in range(3))
    out = flash_attention_xla(q, k, v, True, None, None, 64, 64)
    exp = ref.attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)

    def loss_flash(q_):
        return jnp.sum(flash_attention_xla(q_, k, v, True, None, None, 64, 64) ** 2)

    def loss_ref(q_):
        return jnp.sum(ref.attention(q_, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=5e-3,
                               atol=5e-3)

    def loss_flash_kv(kv):
        k_, v_ = kv
        return jnp.sum(flash_attention_xla(q, k_, v_, True, None, None, 64, 64) ** 2)

    def loss_ref_kv(kv):
        k_, v_ = kv
        return jnp.sum(ref.attention(q, k_, v_, causal=True) ** 2)

    gk1, gv1 = jax.grad(loss_flash_kv)((k, v))
    gk2, gv2 = jax.grad(loss_ref_kv)((k, v))
    np.testing.assert_allclose(np.asarray(gk1), np.asarray(gk2), rtol=5e-3,
                               atol=5e-3)
    np.testing.assert_allclose(np.asarray(gv1), np.asarray(gv2), rtol=5e-3,
                               atol=5e-3)


def test_flash_xla_gqa_softcap_window():
    from repro.models.flash_xla import flash_attention_xla
    B, K, G, L, hd = 1, 2, 3, 128, 32
    H = K * G
    q = jnp.asarray(rand(B, H, L, hd))
    k = jnp.asarray(rand(B, K, L, hd))
    v = jnp.asarray(rand(B, K, L, hd))
    out = flash_attention_xla(q, k, v, True, 32, 20.0, 64, 64)
    # oracle: broadcast GQA, apply softcap+window
    kb = jnp.repeat(k, G, axis=1)
    vb = jnp.repeat(v, G, axis=1)
    import math
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kb) / math.sqrt(hd)
    logits = jnp.tanh(logits / 20.0) * 20.0
    qi = jnp.arange(L)[:, None]
    kj = jnp.arange(L)[None, :]
    m = (kj <= qi) & (kj > qi - 32)
    logits = jnp.where(m[None, None], logits, -1e30)
    exp = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), vb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


# --------------------------------------------------------------------------
# flash-decode kernel (serving hot loop)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,K,S,hd", [(2, 8, 2, 256, 64), (1, 4, 4, 512, 128),
                                        (3, 6, 3, 384, 64)])
def test_flash_decode_kernel(B, H, K, S, hd):
    from repro.kernels import decode_attention as da
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, K, S, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, K, S, hd)).astype(np.float32))
    lengths = jnp.asarray(rng.integers(1, S, B), jnp.int32)  # ragged slots
    out = da.flash_decode(q, k, v, lengths, block_k=128)
    exp = da.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


def test_flash_decode_full_length():
    from repro.kernels import decode_attention as da
    rng = np.random.default_rng(1)
    B, H, K, S, hd = 2, 4, 2, 128, 32
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, K, S, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, K, S, hd)).astype(np.float32))
    lengths = jnp.full((B,), S, jnp.int32)
    out = da.flash_decode(q, k, v, lengths, block_k=64)
    exp = da.decode_attention_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


# --------------------------------------------------------------------------
# paged flash-decode kernel (page-table KV gather, serving hot loop)
# --------------------------------------------------------------------------
def _paged_setup(rng, B, K, hd, pt, n_pages, lengths):
    """Random page pools + per-seq page tables with shuffled physical pages."""
    kp = jnp.asarray(rng.standard_normal((n_pages, K, pt, hd)).astype(np.float32))
    vp = jnp.asarray(rng.standard_normal((n_pages, K, pt, hd)).astype(np.float32))
    max_pages = max(-(-int(l) // pt) for l in lengths)
    table = np.full((B, max_pages), -1, np.int32)
    perm = rng.permutation(n_pages)
    i = 0
    for b in range(B):
        need = -(-int(lengths[b]) // pt)
        table[b, :need] = perm[i:i + need]
        i += need
    assert i <= n_pages, "test setup: not enough physical pages"
    return kp, vp, jnp.asarray(table)


@pytest.mark.parametrize("B,H,K,hd", [(2, 8, 2, 64), (1, 4, 4, 128),
                                      (3, 6, 3, 64), (2, 4, 1, 32),
                                      (2, 24, 8, 128)])   # minitron: G 3
@pytest.mark.parametrize("pt", [8, 16, 64])
def test_paged_flash_decode_vs_ref(B, H, K, hd, pt):
    """Golden test over ragged lengths × GQA group counts × page sizes."""
    from repro.kernels import paged_decode_attention as pda
    from repro.kernels import ref
    rng = np.random.default_rng(B * 1000 + pt)
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    lengths = rng.integers(1, 160, B).astype(np.int32)
    kp, vp, table = _paged_setup(rng, B, K, hd, pt, n_pages=96,
                                 lengths=lengths)
    out = pda.paged_flash_decode(q, kp, vp, table, jnp.asarray(lengths),
                                 interpret=True)
    k_dense = pda.gather_pages(kp, table)
    v_dense = pda.gather_pages(vp, table)
    exp = ref.decode_attention(q, k_dense, v_dense, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


def test_paged_flash_decode_page_boundary_lengths():
    """Lengths exactly on page boundaries + single-token sequences."""
    from repro.kernels import paged_decode_attention as pda
    rng = np.random.default_rng(7)
    B, H, K, hd, pt = 4, 4, 2, 32, 8
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    lengths = np.array([1, pt, 2 * pt, 3 * pt - 1], np.int32)
    kp, vp, table = _paged_setup(rng, B, K, hd, pt, n_pages=32,
                                 lengths=lengths)
    out = pda.paged_flash_decode(q, kp, vp, table, jnp.asarray(lengths),
                                 interpret=True)
    exp = pda.paged_decode_attention_ref(q, kp, vp, table, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


def _garbage_setup(rng, B, K, hd, pt, max_pages, lengths, quant):
    """Pools and a page table where every K/V row the kernel must not read
    is NaN (float pools) or has a NaN scale (int8 pools): rows past each
    length in its last page, two reserved pages mapped past each length,
    and page 0, which unmapped (-1) entries clamp to. Returns the clean
    pools (garbage zeroed) for the oracle, then the planted ones."""
    from repro.serve import kvquant
    need = [-(-int(n) // pt) for n in lengths]
    n_pages = 1 + sum(need) + 2 * B
    clean = [rng.standard_normal((n_pages, K, pt, hd)).astype(np.float32)
             for _ in range(2)]
    table = np.full((B, max_pages), -1, np.int32)
    ids = iter(rng.permutation(np.arange(1, n_pages)))
    bad = np.zeros((n_pages, pt), bool)                   # (page, row)
    bad[0] = True
    for b in range(B):
        n = need[b] + min(2, max_pages - need[b])     # live + reserved
        table[b, :n] = [next(ids) for _ in range(n)]
        for j in range(n):
            rows = np.arange(pt) + j * pt >= lengths[b]
            bad[table[b, j]] |= rows
    for x in clean:
        x[np.broadcast_to(bad[:, None, :, None], x.shape)] = 0.0
    if not quant:
        planted = [np.where(bad[:, None, :, None], np.nan, x) for x in clean]
        return ([jnp.asarray(x) for x in clean],
                [jnp.asarray(x) for x in planted], jnp.asarray(table))
    qs = [kvquant.quantize_pages(jnp.asarray(x)) for x in clean]
    dead = ~np.isin(np.arange(n_pages), [table[b, j] for b in range(B)
                                         for j in range(need[b])])
    planted = [(x, jnp.where(jnp.asarray(dead)[:, None], jnp.nan, s))
               for x, s in qs]
    return qs, planted, jnp.asarray(table)


@pytest.mark.parametrize("case", [
    # 129 pages of 16: the last block of 8 pages holds one (ragged)
    dict(H=4, K=2, hd=32, pt=16, max_pages=129, lengths=[2064, 1000, 5]),
    # inactive slots (length 0) beside full-length ones
    dict(H=4, K=2, hd=32, pt=16, max_pages=24, lengths=[0, 384, 0, 384]),
    # block edges: 8 pages of 16 make a block of 128 positions
    dict(H=6, K=3, hd=32, pt=16, max_pages=24,
         lengths=[127, 128, 129, 256, 1]),
    # the int8 pool under the same walk
    dict(H=4, K=2, hd=32, pt=16, max_pages=129, lengths=[0, 129, 2064],
         quant=True),
    # minitron's heads: K 8, G 3, head dim 128
    dict(H=24, K=8, hd=128, pt=16, max_pages=40, lengths=[640, 129]),
], ids=["ragged_129_pages", "inactive_slots", "block_edges", "int8",
        "minitron_heads"])
def test_paged_flash_decode_block_edges(case):
    """Blocks of whole pages: ragged last blocks, lengths at and around a
    block's edge, inactive slots (zeros out) — with every row the kernel
    must not read planted as NaN, and the output finite and equal to the
    oracle on the clean pools."""
    from repro.kernels import paged_decode_attention as pda
    quant = case.get("quant", False)
    lengths = np.asarray(case["lengths"], np.int32)
    B, H, K, hd, pt = len(lengths), case["H"], case["K"], case["hd"], case["pt"]
    rng = np.random.default_rng(int(lengths.sum()))
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    clean, planted, table = _garbage_setup(rng, B, K, hd, pt,
                                           case["max_pages"], lengths, quant)
    lens = jnp.asarray(lengths)
    if quant:
        (kq, ks), (vq, vs) = planted
        out = pda.paged_flash_decode(q, kq, vq, table, lens, k_scale=ks,
                                     v_scale=vs, interpret=True)
        (kq, ks), (vq, vs) = clean
        exp = pda.paged_decode_attention_ref(q, kq, vq, table, lens,
                                             k_scale=ks, v_scale=vs)
    else:
        out = pda.paged_flash_decode(q, *planted, table, lens,
                                     interpret=True)
        exp = pda.paged_decode_attention_ref(q, *clean, table, lens)
    exp = jnp.where(lens[:, None, None] > 0, exp, 0.0)   # inactive: zeros
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


def test_walk_table_copies_only_live_pages():
    """The walk's table changes a block column's page only where the new
    page holds live positions, so the pipeline, which copies a column when
    its page changes from one grid step to the next, copies nothing past a
    slot's length — reserved pages, unmapped entries and the ragged last
    block's missing columns included — beyond each column's first copy."""
    from repro.kernels import paged_decode_attention as pda
    rng = np.random.default_rng(5)
    B, max_pages, pt, ppb, P = 6, 13, 16, 4, 96     # 13 = 3 blocks + 1
    lengths = np.array([0, 100, 208, 17, 64, 0], np.int32)
    table = np.full((B, max_pages), -1, np.int32)
    ids = iter(rng.permutation(P))
    for b in range(B):
        n = min(max_pages, -(-int(lengths[b]) // pt) + 2)   # + reserved
        table[b, :n] = [next(ids) for _ in range(n)]
    walk = np.asarray(pda.walk_table(jnp.asarray(table), jnp.asarray(lengths),
                                     P, pt, ppb))
    assert walk.shape == table.shape and walk.min() >= 0 and walk.max() < P
    held = [None] * ppb
    copies = 0
    for b in range(B):
        for i in range(-(-max_pages // ppb)):
            for j in range(ppb):
                g = i * ppb + j
                page = walk[b, g if g < max_pages else g - ppb]
                live = g * pt < lengths[b]
                if live:
                    assert page == table[b, g]
                if page != held[j]:
                    assert live or held[j] is None, (b, i, j)
                    copies += 1
                held[j] = page
    n_live = sum(-(-int(n) // pt) for n in lengths)
    assert copies <= n_live + ppb


def test_paged_matches_dense_flash_decode():
    """Same logical cache through the dense and the paged kernels."""
    from repro.kernels import decode_attention as da
    from repro.kernels import paged_decode_attention as pda
    rng = np.random.default_rng(3)
    B, H, K, hd, pt = 2, 8, 2, 64, 16
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    lengths = np.array([37, 61], np.int32)
    kp, vp, table = _paged_setup(rng, B, K, hd, pt, n_pages=16,
                                 lengths=lengths)
    k_dense = pda.gather_pages(kp, table)
    v_dense = pda.gather_pages(vp, table)
    out_paged = pda.paged_flash_decode(q, kp, vp, table, jnp.asarray(lengths),
                                       interpret=True)
    out_dense = da.flash_decode(q, k_dense, v_dense, jnp.asarray(lengths),
                                block_k=pt)
    np.testing.assert_allclose(np.asarray(out_paged), np.asarray(out_dense),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# paged flash-prefill kernel (chunked prefill, cross-chunk causal masking)
# --------------------------------------------------------------------------
def _prefill_setup(rng, K, hd, pt, n_pages, S, max_pages):
    """One sequence's shuffled page list holding S tokens of K/V."""
    kp = jnp.asarray(rng.standard_normal((n_pages, K, pt, hd)).astype(np.float32))
    vp = jnp.asarray(rng.standard_normal((n_pages, K, pt, hd)).astype(np.float32))
    need = -(-S // pt)
    table = np.full((max_pages,), -1, np.int32)
    table[:need] = rng.permutation(n_pages)[:need]
    return kp, vp, jnp.asarray(table)


@pytest.mark.parametrize("C,start", [(1, 0), (1, 13), (3, 5), (3, 0),
                                     (8, 8), (5, 11), (12, 3)])
def test_paged_flash_prefill_vs_oracle(C, start):
    """Chunk queries vs the paged prefix across chunk sizes and offsets —
    including starts that land mid-page (the chunk-boundary causal edge)."""
    from repro.kernels import paged_prefill_attention as ppa
    rng = np.random.default_rng(C * 100 + start)
    K, H, hd, pt = 2, 4, 32, 8
    S = start + C
    kp, vp, table = _prefill_setup(rng, K, hd, pt, n_pages=24, S=S,
                                   max_pages=6)
    q = jnp.asarray(rng.standard_normal((C, H, hd)).astype(np.float32))
    out = ppa.paged_flash_prefill(q, kp, vp, table,
                                  jnp.asarray(start, jnp.int32),
                                  interpret=True)
    exp = ppa.paged_prefill_attention_ref(q, kp, vp, table, start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("chunks", [[1] * 14, [3, 3, 3, 3, 2], [8, 6],
                                    [14], [5, 1, 8]],
                         ids=["ones", "threes", "budget8", "single", "ragged"])
def test_paged_prefill_chunked_matches_single_shot_ref(chunks):
    """Prefilling a sequence in chunks of 1 / 3 / budget-sized slices must
    reproduce the single-shot causal attention reference from kernels/ref.py:
    every chunk attends the *running* paged prefix, so concatenating the
    chunk outputs equals one causal pass over the whole prompt — the
    cross-chunk causal mask is what makes the equality hold."""
    from repro.kernels import paged_prefill_attention as ppa
    from repro.kernels import ref
    rng = np.random.default_rng(42)
    K, H, hd, pt = 2, 4, 32, 8
    S = sum(chunks)
    G = H // K
    kp0, vp0, table = _prefill_setup(rng, K, hd, pt, n_pages=16, S=S,
                                     max_pages=4)
    q_full = jnp.asarray(rng.standard_normal((S, H, hd)).astype(np.float32))
    k_full = jnp.asarray(rng.standard_normal((S, K, hd)).astype(np.float32))
    v_full = jnp.asarray(rng.standard_normal((S, K, hd)).astype(np.float32))

    # chunked: scatter each chunk's K/V into the pages, then attend it
    from repro.serve.paged_step import scatter_chunk
    kp, vp = kp0, vp0
    outs, start = [], 0
    for C in chunks:
        sl = slice(start, start + C)
        kp = scatter_chunk(kp, k_full[sl], table,
                           jnp.asarray(start, jnp.int32), pt)
        vp = scatter_chunk(vp, v_full[sl], table,
                           jnp.asarray(start, jnp.int32), pt)
        outs.append(ppa.paged_flash_prefill(q_full[sl], kp, vp, table,
                                            jnp.asarray(start, jnp.int32),
                                            interpret=True))
        start += C
    got = jnp.concatenate(outs, axis=0)                      # [S, H, hd]

    # single-shot reference: ref.attention with GQA heads broadcast
    qb = jnp.transpose(q_full, (1, 0, 2))[None]              # [1, H, S, hd]
    kb = jnp.repeat(jnp.transpose(k_full, (1, 0, 2)), G, axis=0)[None]
    vb = jnp.repeat(jnp.transpose(v_full, (1, 0, 2)), G, axis=0)[None]
    exp = jnp.transpose(ref.attention(qb, kb, vb, causal=True)[0], (1, 0, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)

    # the KV pages themselves must hold the full prompt's K/V exactly
    from repro.kernels.paged_decode_attention import gather_pages
    np.testing.assert_allclose(
        np.asarray(gather_pages(kp, table[None])[0][:, :S]),
        np.asarray(jnp.transpose(k_full, (1, 0, 2))), rtol=1e-6, atol=1e-6)


def test_paged_prefill_chunk_boundary_mid_page():
    """A chunk that starts and ends mid-page must mask exactly: the last
    query of chunk i sees one more key than the first of chunk i+1 sees
    minus its own — verified against the oracle at the boundary pair."""
    from repro.kernels import paged_prefill_attention as ppa
    rng = np.random.default_rng(9)
    K, H, hd, pt = 2, 4, 32, 8
    kp, vp, table = _prefill_setup(rng, K, hd, pt, n_pages=8, S=13,
                                   max_pages=2)
    for C, start in [(6, 0), (7, 6)]:     # 13 tokens split mid-page at 6
        q = jnp.asarray(rng.standard_normal((C, H, hd)).astype(np.float32))
        out = ppa.paged_flash_prefill(q, kp, vp, table,
                                      jnp.asarray(start, jnp.int32),
                                      interpret=True)
        exp = ppa.paged_prefill_attention_ref(q, kp, vp, table, start)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# quantized paged kernels: int8 pages + per-(page, kv-head) scales
# --------------------------------------------------------------------------
def _quantize_setup(kp, vp):
    from repro.serve import kvquant
    kq, ks = kvquant.quantize_pages(kp)
    vq, vs = kvquant.quantize_pages(vp)
    return kq, ks, vq, vs


def test_paged_flash_decode_quantized_matches_ref():
    """The in-VMEM dequant path must agree with the dense oracle operating
    on the SAME dequantized pages — only flash-vs-softmax numerics differ."""
    from repro.kernels import paged_decode_attention as pda
    rng = np.random.default_rng(21)
    B, H, K, hd, pt = 3, 6, 3, 64, 8
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    lengths = rng.integers(1, 100, B).astype(np.int32)
    kp, vp, table = _paged_setup(rng, B, K, hd, pt, n_pages=64,
                                 lengths=lengths)
    kq, ks, vq, vs = _quantize_setup(kp, vp)
    out = pda.paged_flash_decode(q, kq, vq, table, jnp.asarray(lengths),
                                 k_scale=ks, v_scale=vs, interpret=True)
    exp = pda.paged_decode_attention_ref(q, kq, vq, table,
                                         jnp.asarray(lengths),
                                         k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)
    # and the quantized result tracks the full-precision one within the
    # int8 error budget (absmax/127 per element on K and V)
    full = pda.paged_flash_decode(q, kp, vp, table, jnp.asarray(lengths),
                                  interpret=True)
    assert float(jnp.max(jnp.abs(out - full))) < 0.15


def test_paged_flash_prefill_quantized_matches_ref():
    from repro.kernels import paged_prefill_attention as ppa
    rng = np.random.default_rng(22)
    K, H, hd, pt, C, start = 2, 4, 32, 8, 5, 11
    kp, vp, table = _prefill_setup(rng, K, hd, pt, n_pages=24, S=start + C,
                                   max_pages=6)
    kq, ks, vq, vs = _quantize_setup(kp, vp)
    q = jnp.asarray(rng.standard_normal((C, H, hd)).astype(np.float32))
    out = ppa.paged_flash_prefill(q, kq, vq, table,
                                  jnp.asarray(start, jnp.int32),
                                  k_scale=ks, v_scale=vs, interpret=True)
    exp = ppa.paged_prefill_attention_ref(q, kq, vq, table, start,
                                          k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-3,
                               atol=2e-3)
    full = ppa.paged_flash_prefill(q, kp, vp, table,
                                   jnp.asarray(start, jnp.int32),
                                   interpret=True)
    assert float(jnp.max(jnp.abs(out - full))) < 0.15


def test_quantized_kernels_require_scale_pairs():
    from repro.kernels import paged_decode_attention as pda
    from repro.kernels import paged_prefill_attention as ppa
    rng = np.random.default_rng(23)
    B, H, K, hd, pt = 1, 2, 1, 16, 4
    q = jnp.asarray(rng.standard_normal((B, H, hd)).astype(np.float32))
    lengths = np.array([4], np.int32)
    kp, vp, table = _paged_setup(rng, B, K, hd, pt, n_pages=4,
                                 lengths=lengths)
    ks = jnp.ones((4, K), jnp.float32)
    with pytest.raises(ValueError):
        pda.paged_flash_decode(q, kp, vp, table, jnp.asarray(lengths),
                               k_scale=ks, interpret=True)
    with pytest.raises(ValueError):
        ppa.paged_flash_prefill(q[0], kp, vp, table[0], jnp.asarray(0),
                                v_scale=ks, interpret=True)
