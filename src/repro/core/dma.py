"""hero_memcpy — the unified DMA API (HEROv2 §2.4) on TPU primitives.

The paper organizes DMA functions along three axes: direction
(host2dev/dev2host), synchronicity (blocking / _async + wait), and
dimensionality (1D/2D scatter-gather). On TPU:

* *host↔device* copies are host-level (``jax.device_put`` / ``np.asarray``) —
  JAX's async dispatch gives the `_async` semantics for free; the returned
  handle's ``wait()`` is ``block_until_ready``.
* *HBM↔VMEM* copies inside kernels are ``pltpu.make_async_copy`` (TPU) with a
  Ref-assignment fallback that is exact in interpret mode — this is the DMA
  engine the AutoDMA planner programs via BlockSpecs; the explicit API here is
  what *handwritten* kernels (the paper's baseline) use.
* 2-D scatter-gather (``hero_memcpy2d_*``) strides the source/destination the
  way the paper's tiling code gathers matrix tiles row-by-row.

Every function is usable under jit; the host-level ones also work eagerly.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------------------------
# host-level (outside kernels): host DRAM <-> device HBM
# --------------------------------------------------------------------------
@dataclasses.dataclass
class TransferHandle:
    """The paper's 'unique transfer identifier' for _async variants.

    ``wait()`` is idempotent (re-waiting a completed transfer is a no-op that
    returns the same value) and ``nbytes`` carries the transfer size for
    hero_perf-style traffic counters (the swap tier sums these).
    ``t_start``/``t_done`` stamp issue and completion on the handle's own
    clock (the ``clock=`` passed to the ``_async`` constructor, defaulting to
    ``time.perf_counter``): the serve-layer tracer renders the async window
    between them on its dma track, so DMA/compute overlap is *observed* from
    the handle, never guessed. The clock is per-handle — two engines with
    different injected clocks never stamp each other's transfers.
    Observational only — nothing reads the stamps to make decisions.
    """
    value: object
    _id: int
    nbytes: int = 0
    t_start: float = 0.0
    t_done: float = 0.0
    clock: Callable[[], float] = time.perf_counter

    def wait(self):
        jax.block_until_ready(self.value)
        if self.t_done == 0.0:
            self.t_done = self.clock()
        return self.value


_NEXT_ID = [0]


def _nbytes(v) -> int:
    try:
        return int(v.size) * int(v.dtype.itemsize)
    except (AttributeError, TypeError):
        return 0


def _handle(v, clock: Optional[Callable[[], float]] = None) -> TransferHandle:
    _NEXT_ID[0] += 1
    clk = clock if clock is not None else time.perf_counter
    return TransferHandle(v, _NEXT_ID[0], _nbytes(v), t_start=clk(),
                          clock=clk)


def hero_memcpy_host2dev(dst_sharding, src) -> jax.Array:
    """Blocking host→device; ``dst_sharding`` may be None (default device)."""
    out = jax.device_put(src, dst_sharding)
    jax.block_until_ready(out)
    return out


def hero_memcpy_host2dev_async(dst_sharding, src,
                               clock: Optional[Callable[[], float]] = None,
                               ) -> TransferHandle:
    return _handle(jax.device_put(src, dst_sharding), clock=clock)


def hero_memcpy_dev2host(dst: Optional[np.ndarray], src: jax.Array) -> np.ndarray:
    arr = np.asarray(jax.device_get(src))
    if dst is not None:
        np.copyto(dst, arr)
        return dst
    return arr


def hero_memcpy_dev2host_async(src: jax.Array,
                               clock: Optional[Callable[[], float]] = None,
                               ) -> TransferHandle:
    src.copy_to_host_async()
    return _handle(src, clock=clock)


def hero_memcpy_wait(handle: TransferHandle):
    """Guarantees transfer completion before the data can be used."""
    return handle.wait()


def hero_memcpy_wait_all(handles) -> list:
    """Wait a batch of handles (all transfers were already in flight, so the
    total wait is the slowest transfer, not the sum — the double-buffering
    contract the swap tier relies on)."""
    return [h.wait() for h in handles]


# --------------------------------------------------------------------------
# kernel-level (inside pallas): HBM/ANY <-> VMEM — the cluster DMA engine
# --------------------------------------------------------------------------
def copy_async(src_ref, dst_ref, sem=None):
    """Start an async block copy; returns an object with ``.wait()``.

    On TPU this is the real DMA engine (``pltpu.make_async_copy``); in
    interpret mode / CPU the copy happens synchronously but the API shape is
    identical, so kernel code is portable (the paper's 'unified over all
    accelerators with per-accelerator optimized implementation').
    """
    if sem is not None:
        cp = pltpu.make_async_copy(src_ref, dst_ref, sem)
        cp.start()
        return cp

    class _Done:
        def wait(self):
            return None
    dst_ref[...] = src_ref[...]
    return _Done()


def hero_memcpy2d(dst_ref, src_ref, rows: int, row_bytes_elems: int,
                  src_row_stride: int, dst_row_stride: int,
                  src_off: int = 0, dst_off: int = 0):
    """2-D scatter-gather copy: N sequences of B elements with per-row strides
    (paper: 'copy N sequences of B bytes ... apply a different address offset
    after each sequence'). Refs are 1-D views; offsets/strides in elements.

    Inside Pallas this lowers to a fori_loop of dynamic slices — one DMA burst
    per row, exactly the burst accounting bench_autodma measures.
    """
    import jax.lax as lax

    def body(i, _):
        s = src_off + i * src_row_stride
        d = dst_off + i * dst_row_stride
        from jax.experimental import pallas as pl
        dst_ref[pl.dslice(d, row_bytes_elems)] = src_ref[pl.dslice(s, row_bytes_elems)]
        return _

    lax.fori_loop(0, rows, body, 0)


# jnp oracle for tests: identical semantics on plain arrays
def memcpy2d_ref(dst: np.ndarray, src: np.ndarray, rows: int, elems: int,
                 src_stride: int, dst_stride: int, src_off=0, dst_off=0) -> np.ndarray:
    dst = np.array(dst)
    for i in range(rows):
        s = src_off + i * src_stride
        d = dst_off + i * dst_stride
        dst[d:d + elems] = src[s:s + elems]
    return dst
