"""A block's layout in, the weights of the system under test out.

``make_params`` makes the weights from the seed on the device, in one jitted
call, as float32 (the type the program stores and serves them in), laid out
as the program takes them: the leaves and their shapes come from the block
the configuration names (``bench/blocks/<block>.py``, ``layout``). The
reference (``bench/reference``) reads the same arrays; the program's own
initialiser is never called, so nothing the reference reads was made by it.

Each leaf is drawn from the seed's key folded with its path, so a leaf's
values depend on its path and shape alone. A path in a later group or
pattern position is prefixed with ``<group>.<position>/``, so two groups
never share values and the first group keeps its plain paths.
"""
from __future__ import annotations

import hashlib
import math
from typing import Dict

import jax
import jax.numpy as jnp


def _leaf(key, shape, kind):
    n = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        return 1.0 + 0.1 * n
    if kind == "bias":
        return 0.1 * n
    if kind == "embed":
        return 0.02 * n
    return n / math.sqrt(kind)          # a matrix: 1/sqrt(fan_in)


def _nest(flat: Dict) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        *parents, name = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = v
    return out


def make_params(layout: Dict, seed: int):
    """The weights of ``layout`` (a block's ``layout(conf)``) for ``seed``,
    made on the default device in one call."""
    def make(key):
        def leaves(shapes, prefix=""):
            return _nest({path: _leaf(
                jax.random.fold_in(key, _path_id(prefix + path)), shape, kind)
                for path, (shape, kind) in shapes.items()})
        params = leaves(layout["top"])
        params["groups"] = [
            tuple(leaves(pos, f"{g}.{i}/" if (g, i) != (0, 0) else "")
                  for i, pos in enumerate(group))
            for g, group in enumerate(layout["groups"])]
        return params

    return jax.jit(make)(jax.random.PRNGKey(jax_seed(seed)))


def _path_id(path: str) -> int:
    return int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``seed``, which may exceed what 32 signed bits hold."""
    return int.from_bytes(
        hashlib.sha256(str(int(seed)).encode()).digest()[:4], "little") >> 1
