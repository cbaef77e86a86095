"""Pipeline parallelism over a mesh axis — GPipe microbatch schedule in
shard_map with collective_permute stage boundaries.

Mapping HEROv2's multi-FPGA scale-out (FMC/QSFP+ chip-to-chip links) to TPU:
pipeline stages ≈ FPGAs, the stage boundary ≈ the chip-to-chip link, and the
microbatch rotation ≈ streaming bursts across it. We implement the classic
circular-pipeline formulation: all stages run the SAME program on their
layer-shard; activations rotate by collective_permute; M microbatches over
S stages take S+M−1 ticks with bubble fraction (S−1)/(S+M−1).

This is an optional execution mode (config.pipeline_stages > 1, mapped onto
the 'pod' or 'model' axis) — the dry-run exercises it for one cell and
tests/test_pipeline.py checks numerical equivalence vs the unpipelined model.
The implementation is deliberately self-contained: it pipelines any
``layer_fn(params_slice, x) -> x`` stack whose params carry a leading
layer axis.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(layer_fn: Callable[[Any, jax.Array], jax.Array],
          mesh: Mesh, stage_axis: str, n_layers: int):
    """Build pipelined_apply(stacked_params, x_microbatched) under shard_map.

    stacked_params: leading axis = n_layers, sharded over stage_axis
    (layers_per_stage = n_layers / S contiguous layers per stage).
    x: [M, mb, ...] microbatches (M ≥ S for reasonable bubble).
    Returns [M, mb, ...] outputs.
    """
    S = mesh.shape[stage_axis]
    assert n_layers % S == 0, (n_layers, S)
    per_stage = n_layers // S

    def stage_fwd(params_stage, xs):  # runs per-device on its layer shard
        def apply_stage(x):
            def body(h, lp):
                return layer_fn(lp, h), None
            h, _ = jax.lax.scan(body, x, params_stage)
            return h

        M = xs.shape[0]
        stage = jax.lax.axis_index(stage_axis)
        n_ticks = M + S - 1

        def _varying(a):
            # scan carries become stage-varying after ppermute; the initial
            # value must carry the same vma type
            return jax.lax.pcast(a, (stage_axis,), to="varying")

        buf = _varying(jnp.zeros_like(xs[0]))

        def tick(carry, t):
            buf, ys = carry
            # stage 0 injects microbatch t (if any); others take the rotated input
            inject = jnp.where(t < M, t, M - 1)
            x_in = jnp.where(stage == 0, xs[inject], buf)
            y = apply_stage(x_in)
            # rotate stage s -> s+1
            buf_next = jax.lax.ppermute(
                y, stage_axis, [(i, (i + 1) % S) for i in range(S)])
            # last stage emits microbatch (t - (S-1)) at tick t
            emit_idx = t - (S - 1)
            ys = jnp.where(
                (stage == S - 1) & (emit_idx >= 0),
                ys.at[jnp.clip(emit_idx, 0, M - 1)].set(y), ys)
            return (buf_next, ys), None

        ys0 = _varying(jnp.zeros_like(xs))
        (_, ys), _ = jax.lax.scan(tick, (buf, ys0), jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast via masked psum
        # (ppermute requires unique sources — 1→all is not a permutation)
        ys = jax.lax.psum(jnp.where(stage == S - 1, ys, jnp.zeros_like(ys)),
                          stage_axis)
        return ys

    pspec_params = P(stage_axis)   # leading layer axis sharded into stages
    pspec_x = P()                  # microbatches replicated across stages

    return jax.shard_map(stage_fwd, mesh=mesh,
                         in_specs=(pspec_params, pspec_x),
                         out_specs=pspec_x)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    """GPipe bubble: (S−1)/(S+M−1) — the §Perf napkin number for PP cells."""
    return (n_stages - 1) / (n_stages + n_microbatches - 1)
