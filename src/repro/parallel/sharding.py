"""Logical-axis sharding rules (MaxText-style) for the (pod, data, model) mesh.

Model code annotates params/activations with *logical* axis names; this module
resolves them to mesh ``PartitionSpec``s under the active rule set. One model
definition thus serves every parallelism layout — DP/FSDP over ("pod","data"),
TP/EP/SP over "model" — and a rule override is all a hillclimb iteration needs
to re-shard (the §Perf loop's cheapest lever).

Robustness rule: a logical axis is only bound to mesh axes whose product
divides the array dimension; otherwise the binding is *dropped for that
array* (e.g. qwen2's 14 heads on a 16-way model axis stay replicated while
its flat 896-wide projections shard fine). This mirrors GSPMD best practice
and keeps every (arch × mesh) cell compilable — a dry-run failure is a bug.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axes (in order; filtered by mesh presence)
DEFAULT_RULES: Tuple[Tuple[str, Optional[Tuple[str, ...]]], ...] = (
    ("batch", ("pod", "data")),      # DP over pods × data axis
    ("embed_fsdp", ("data",)),       # ZeRO-3 parameter shard axis
    ("heads_tp", ("model",)),        # Megatron column split
    ("kv_heads_tp", ("model",)),
    ("vocab_tp", ("model",)),
    ("mlp_tp", ("model",)),
    ("expert", ("model",)),          # EP
    ("kv_seq", None),                # SP: flipped to ("model",) per-config
    ("seq_sp", None),                # context-parallel prefill (hillclimb lever)
    ("stage", ("pod",)),             # pipeline stages (parallel/pipeline.py)
    ("layers", None),                # scan-stacked leading axis
)


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Dict[str, Optional[Tuple[str, ...]]] = dict(DEFAULT_RULES)


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Mesh, overrides: Optional[Dict[str, Optional[Tuple[str, ...]]]] = None):
    """Activate a mesh + (optionally overridden) logical rules.

    Also enters the mesh as the ambient jax mesh so ``jax.jit`` +
    ``with_sharding_constraint`` resolve named axes.
    """
    prev_mesh, prev_rules = _CTX.mesh, _CTX.rules
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        with mesh:
            yield mesh
    finally:
        _CTX.mesh, _CTX.rules = prev_mesh, prev_rules


def auto_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the logical-rule layer
    places arrays through ``with_sharding_constraint``, which rejects the
    ``Explicit`` axes ``jax.make_mesh`` gives by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def axis_size(mesh_axes: Sequence[str], mesh: Mesh) -> int:
    n = 1
    for a in mesh_axes:
        n *= mesh.shape[a]
    return n


def resolve(axes: Sequence[Optional[str]], shape: Optional[Sequence[int]] = None,
            mesh: Optional[Mesh] = None) -> P:
    """Logical axes -> PartitionSpec under the active rules/mesh.

    Filters mesh axes absent from the mesh, drops bindings that don't divide
    the dimension, and never reuses a mesh axis across dimensions.
    """
    mesh = mesh or _CTX.mesh
    rules = _CTX.rules
    used: set = set()
    out = []
    for i, name in enumerate(axes):
        if name is None or mesh is None:
            out.append(None)
            continue
        pref = rules.get(name)
        if pref is None:
            out.append(None)
            continue
        chosen = tuple(a for a in pref if a in mesh.shape and a not in used)
        if not chosen:
            out.append(None)
            continue
        if shape is not None:
            n = axis_size(chosen, mesh)
            if shape[i] % n != 0:
                # try the longest divisible prefix/suffix of the binding
                chosen2 = tuple(a for a in chosen if shape[i] % mesh.shape[a] == 0)[:1]
                if chosen2 and shape[i] % axis_size(chosen2, mesh) == 0:
                    chosen = chosen2
                else:
                    out.append(None)
                    continue
        used.update(chosen)
        out.append(chosen if len(chosen) > 1 else chosen[0])
    return P(*out)


def constrain(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = resolve(axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(axes: Sequence[Optional[str]], shape: Optional[Sequence[int]] = None,
                   mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or _CTX.mesh
    return NamedSharding(mesh, resolve(axes, shape, mesh))


def tree_shardings(axes_tree, shape_tree=None, mesh: Optional[Mesh] = None):
    """Axes pytree (+ optional shapes) -> NamedSharding pytree (for jit
    in_shardings / device_put of the whole param tree)."""
    mesh = mesh or _CTX.mesh
    is_axes = lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)
    if shape_tree is None:
        return jax.tree_util.tree_map(
            lambda ax: NamedSharding(mesh, resolve(ax, None, mesh)),
            axes_tree, is_leaf=is_axes)
    return jax.tree_util.tree_map(
        lambda ax, sh: NamedSharding(mesh, resolve(ax, tuple(sh), mesh)),
        axes_tree, shape_tree, is_leaf=is_axes)


def stack_axes(axes: Tuple[Optional[str], ...], n_lead: int = 1) -> Tuple[Optional[str], ...]:
    """Prepend 'layers' axes for scan-stacked params."""
    return ("layers",) * n_lead + tuple(axes)


# -- serving tensor parallelism (serve/executor.py) --------------------------
TP_AXIS = "tp"


def tp_mesh(tp: int, axis: str = TP_AXIS) -> Mesh:
    """A 1-D tensor-parallel mesh over the first ``tp`` local devices.

    The serving executor shards KV pages (and the paged-attention head walk)
    over this axis while keeping page tables, the allocator, and all weights
    replicated — see serve/executor.py. On a CPU container, force multiple
    host devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    *before* jax initialises.
    """
    devs = jax.devices()
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if len(devs) < tp:
        raise ValueError(
            f"tp={tp} needs {tp} devices but only {len(devs)} are visible "
            "(on CPU, set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{tp} before importing jax)")
    return Mesh(np.asarray(devs[:tp]), (axis,))
