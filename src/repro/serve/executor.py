"""Serving executor: compiled model steps, device-side sampling, the tp mesh.

The middle of the HEROv2-style decomposition (scheduler → cache manager →
executor): everything that *touches the device* lives here. The scheduler
(serve/scheduler.py) decides which sequences prefill, chunk, or decode each
iteration; this module owns the compiled TargetRegions that execute those
decisions and the device↔host data discipline around them:

  * **Token selection is folded into the jitted step.** Every region returns
    sampled token *ids* (greedy argmax over the logits), not logits — the
    [vocab]-sized activations never cross the PCIe analogue. The scheduler
    collects the per-dispatch id arrays and materialises them with ONE
    ``fetch_token_ids`` call per engine iteration (one device→host transfer,
    replacing the four scattered per-slot ``int(jnp.argmax(...))`` syncs the
    monolithic engine carried; regression-tested in
    tests/test_scheduler_properties.py).
  * **Tensor parallelism** (``tp > 1``): the paged regions are built under
    ``parallel.sharding.use_mesh`` and wrapped in ``shard_map`` over a
    1-D ``tp`` mesh axis. KV pages shard along their kv-head axis (axis 2 of
    every [count, P, K, pt, hd] pool leaf); page tables, lengths, tokens,
    weights, and the host-side allocator stay replicated. Inside the shard,
    paged_decode_attention / paged_prefill_attention run on their head slice
    and a single all-gather of per-head partial outputs rebuilds the full
    head dimension (a concatenation, never a reduction — so on the CPU
    tp=N greedy streams are bit-identical to tp=1; on TPU chips the streams
    have differed within bf16 tolerance, cause not yet traced).

Ownership boundaries & invariants:

  * This module owns **compiled regions + the mesh + the sampler** — no
    scheduling state, no page accounting. It never mutates the cache
    manager; updated page pools are returned to the caller.
  * The jit cache is shared process-wide (``_REGION_CACHE``): step functions
    are pure in (cfg, page_tokens, tp), so every Engine over the same config
    reuses the same compiled artifact (property tests construct dozens).
  * ``fetch_token_ids`` is the ONLY device→host path for sampled ids, and
    ``stats["token_fetches"]`` counts every call — the one-transfer-per-
    iteration property is asserted against it.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.offload import TargetRegion
from repro.models import transformer
from repro.parallel import sharding
from repro.serve import paged_step, trace
from repro.train import step as steps

# KV-page pool leaves are [count, P, K, pt, hd]: shard the kv-head axis
_PAGES_SPEC = P(None, None, sharding.TP_AXIS)

# Step functions are pure in (cfg, page_tokens, tp); sharing their
# TargetRegions across Engine instances shares the jit cache — property tests
# and benches construct many engines over the same config, and retracing the
# model per engine dominated their wall time.
_REGION_CACHE: Dict[Tuple, TargetRegion] = {}


def _cached_region(name: str, key: Tuple, make: Callable) -> TargetRegion:
    try:
        full_key = (name,) + key
        hash(full_key)
    except TypeError:
        return TargetRegion(make(), name=name)
    reg = _REGION_CACHE.get(full_key)
    if reg is None:
        reg = TargetRegion(make(), name=name)
        _REGION_CACHE[full_key] = reg
    return reg


class Executor:
    """Compiled prefill/decode dispatch for one Engine (dense or paged).

    The scheduler calls the ``decode_* / prefill_*`` methods, each of which
    dispatches one TargetRegion asynchronously and returns device-resident
    sampled ids plus the updated cache arrays; ``fetch_token_ids`` batches
    the iteration's ids into one host transfer.

    Pallas kernels run in interpret mode only when the executor's devices
    are CPUs; on an accelerator they are compiled, and a kernel that does
    not compile fails the step instead of falling back.
    """

    def __init__(self, cfg: transformer.ModelConfig, params, *,
                 paged: bool, chunked: bool = False, page_tokens: int = 16,
                 tp: int = 1):
        self.cfg = cfg
        self.params = params
        self.paged = paged
        self.chunked = chunked
        self.page_tokens = page_tokens
        self.tp = int(tp)
        self.stats = {"token_fetches": 0, "tokens_fetched": 0}
        self.bus = None     # MetricsBus, attached by the Engine facade
        self.tracer = trace.null_tracer()   # Tracer, rebound by the facade
        self._inflight: List[Tuple[str, float]] = []  # open device windows
        if self.tp > 1 and not paged:
            raise ValueError("tensor parallelism requires the paged serving "
                             "path (dense slot caches are not head-sharded)")
        if self.tp > 1 and cfg.n_kv % self.tp != 0:
            raise ValueError(
                f"tp={self.tp} must divide the kv-head count ({cfg.n_kv}): "
                "KV pages shard along the kv-head axis")
        self.mesh = sharding.tp_mesh(self.tp) if self.tp > 1 else None
        if self.mesh is not None:
            # weights are replicated on every shard: place them once, not
            # on every dispatch
            self.params = jax.device_put(params, NamedSharding(self.mesh, P()))
        devices = (self.mesh.devices.flat if self.mesh is not None
                   else jax.devices()[:1])
        self.interpret = all(d.platform == "cpu" for d in devices)
        # interpret changes the compiled artifact, so it keys the cache too
        key = (cfg, page_tokens, self.tp, self.interpret)
        if paged:
            self._decode = _cached_region(
                "paged_decode", key, self._make_paged_decode)
            self._prefill_dense = _cached_region(
                "paged_prefill", (cfg,), self._make_prefill_dense)
            if chunked:
                self._prefill_chunk = _cached_region(
                    "paged_prefill_chunk", key, self._make_prefill_chunk)
        else:
            self._decode = _cached_region(
                "dense_decode", (cfg,), self._make_dense_decode)
            # per-slot dense prefill closes over cfg only; cache it too
            self._prefill_slot = _cached_region(
                "dense_prefill_slot", (cfg,), self._make_prefill_slot)

    # -- region builders ---------------------------------------------------
    def _mesh_ctx(self):
        return (sharding.use_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _shard_mapped(self, fn, n_pre: int, n_post: int):
        """Wrap a paged step: pages arg sits between ``n_pre`` replicated
        leading args and ``n_post`` replicated trailing args; sampled ids
        come back replicated, pages stay head-sharded."""
        if self.mesh is None:
            return fn
        in_specs = (P(),) * n_pre + (_PAGES_SPEC,) + (P(),) * n_post
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=(P(), _PAGES_SPEC), check_vma=False)

    def _make_paged_decode(self):
        tp_axis = sharding.TP_AXIS if self.mesh is not None else None
        base = paged_step.make_paged_decode_step(
            self.cfg, self.page_tokens, interpret=self.interpret,
            tp_axis=tp_axis)

        def sampled(params, tokens, pages, page_table, lengths, active):
            logits, pages = base(params, tokens, pages, page_table, lengths,
                                 active)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), pages

        return self._shard_mapped(sampled, n_pre=2, n_post=3)

    def _make_prefill_chunk(self):
        tp_axis = sharding.TP_AXIS if self.mesh is not None else None
        base = paged_step.make_paged_prefill_chunk_step(
            self.cfg, self.page_tokens, interpret=self.interpret,
            tp_axis=tp_axis)

        def sampled(params, tokens, pages, table_row, start):
            logits, pages = base(params, tokens, pages, table_row, start)
            return jnp.argmax(logits[0]).astype(jnp.int32), pages

        return self._shard_mapped(sampled, n_pre=2, n_post=2)

    def _make_prefill_dense(self):
        base = steps.make_prefill_step(self.cfg)

        def sampled(params, tokens, caches):
            logits, caches = base(params, tokens, caches)   # [B, 1, vocab]
            return jnp.argmax(logits[0, -1]).astype(jnp.int32), caches

        return sampled

    def _make_dense_decode(self):
        base = steps.make_decode_step(self.cfg)

        def sampled(params, tokens, caches, cache_pos):
            logits, caches = base(params, tokens, caches, cache_pos)
            return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), caches

        return sampled

    def _make_prefill_slot(self):
        cfg = self.cfg

        def sampled(params, tokens, caches, slot, length):
            logits, new_caches, _ = transformer.forward(
                params, tokens, cfg, caches=caches,
                cache_pos=jnp.zeros((), jnp.int32), mode="prefill")

            # write back only this slot's rows (axis 1 = batch in stacked
            # caches)
            def merge(old, new):
                return jax.lax.dynamic_update_slice_in_dim(
                    old, jax.lax.dynamic_slice_in_dim(new, slot, 1, axis=1)
                    .astype(old.dtype), slot, axis=1)

            merged = jax.tree_util.tree_map(merge, caches, new_caches)
            return (jnp.argmax(logits[slot, length - 1]).astype(jnp.int32),
                    merged)

        return sampled

    # -- dispatch (async — the host thread continues immediately) ----------
    def _note_dispatch(self, kind: str) -> None:
        """Open a device window: jax dispatch is async, so the device is
        (at least potentially) busy from here until this iteration's host
        values land in ``fetch_token_ids`` — which closes every open window
        with observed timestamps (span gaps, not guesses)."""
        if self.tracer.enabled:
            self._inflight.append((kind, self.tracer.now()))
        # host spans entered from here until the next fetch are candidates
        # for the "shadowed" stall bucket (self-gated when tracing is off)
        self.tracer.device_dispatch()

    def decode_paged(self, tokens, pages, page_table, lengths, active):
        with self.tracer.span("dispatch", kind="decode_paged"):
            with self._mesh_ctx():
                out = self._decode(self.params, tokens, pages, page_table,
                                   lengths, active)
            self._note_dispatch("decode_paged")
            return out

    def prefill_chunk(self, tokens, pages, table_row, start):
        with self.tracer.span("dispatch", kind="prefill_chunk"):
            with self._mesh_ctx():
                out = self._prefill_chunk(self.params, tokens, pages,
                                          table_row, start)
            self._note_dispatch("prefill_chunk")
            return out

    def prefill_dense(self, tokens, caches):
        with self.tracer.span("dispatch", kind="prefill_dense"):
            with self._mesh_ctx():
                out = self._prefill_dense(self.params, tokens, caches)
            self._note_dispatch("prefill_dense")
            return out

    def decode_dense(self, tokens, caches, cache_pos):
        with self.tracer.span("dispatch", kind="decode_dense"):
            out = self._decode(self.params, tokens, caches, cache_pos)
            self._note_dispatch("decode_dense")
            return out

    def prefill_slot(self, tokens, caches, slot, length):
        with self.tracer.span("dispatch", kind="prefill_slot"):
            out = self._prefill_slot(self.params, tokens, caches, slot,
                                     length)
            self._note_dispatch("prefill_slot")
            return out

    def compile_decode(self, pool):
        """Compile the paged decode region at ``pool``'s shapes. The
        result's ``as_text()`` is what the device runs: a Pallas kernel
        compiled by Mosaic appears in it as a ``tpu_custom_call``."""
        n = len(pool.lengths)
        args = (jnp.zeros((n, 1), jnp.int32), pool.pages,
                jnp.asarray(pool.device_page_tables()),
                jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool))
        key = tuple((a.shape, str(a.dtype)) for a in jax.tree.leaves(args))
        with self._mesh_ctx():
            return self._decode.lower_compile(self.params, *args, key=key)[1]

    # -- pool placement ----------------------------------------------------
    def shard_pool(self, pool) -> None:
        """Place a paged pool's page arrays on the tp mesh (kv-head axis
        sharded). No-op at tp=1. Host-side state (page tables, allocator,
        lengths) is untouched — it stays replicated by construction."""
        if self.mesh is None:
            return
        ns = NamedSharding(self.mesh, _PAGES_SPEC)
        pool.pages = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, ns), pool.pages)

    def bind_metrics(self, bus) -> None:
        """Attach the engine's MetricsBus; the executor mirrors its transfer
        counters onto it (observe-only — dispatch behaviour is unchanged)."""
        self.bus = bus

    def bind_tracer(self, tracer) -> None:
        """Attach the engine's Tracer: dispatches open ``dispatch`` spans +
        async ``device_step`` windows, and ``fetch_token_ids`` wraps the one
        device→host sync in a ``fetch_tokens`` span (observe-only)."""
        self.tracer = tracer

    # -- the one device→host transfer --------------------------------------
    def fetch_token_ids(self, arrays: Sequence[jax.Array]
                        ) -> List[np.ndarray]:
        """Materialise this iteration's sampled ids in ONE transfer.

        ``arrays`` holds scalars (chunk-completion ids) and/or [B] vectors
        (a decode batch); they are concatenated device-side and fetched with
        a single ``np.asarray``. Returns one host array per input, in order.
        """
        flats = [jnp.ravel(a) for a in arrays]
        joined = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        self.stats["token_fetches"] += 1
        with self.tracer.span("fetch_tokens", arrays=len(arrays)):
            host = np.asarray(joined)
        self.tracer.device_landed()
        if self._inflight:
            # the host values landed: every window opened since the last
            # fetch is now known to have completed — close them at observed
            # time on the device track
            t_end = self.tracer.now()
            for kind, t_begin in self._inflight:
                self.tracer.async_span("device", "device_step", t_begin,
                                       t_end, kind=kind)
            self._inflight.clear()
        self.stats["tokens_fetched"] += int(host.size)
        if self.bus is not None:
            self.bus.set_total("token_fetches", self.stats["token_fetches"])
            self.bus.set_total("tokens_fetched", self.stats["tokens_fetched"])
        out, off = [], 0
        for f in flats:
            out.append(host[off:off + f.size])
            off += f.size
        return out
