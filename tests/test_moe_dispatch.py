"""MoE dispatch equivalence: psum-EP ('gather') vs all-to-all EP ('a2a') vs
the meshless dense path — same math, different collective schedules.
Runs on 8 fake devices in a subprocess (data=2, model=4)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.models import moe
from repro.models.blocks import split_params
from repro.parallel import sharding as shlib

mesh = shlib.auto_mesh((2, 4), ("data", "model"))
cfg = moe.MoeConfig(d_model=32, n_experts=8, top_k=2, d_ff=16, n_shared=1,
                    capacity_factor=2.0)
p, _ = split_params(moe.init_moe(jax.random.PRNGKey(0), cfg))
B, L = 4, 8
x = jnp.asarray(np.random.default_rng(0).standard_normal((B, L, 32)),
                jnp.float32) * 0.5

# reference: meshless dense path
y_ref, aux_ref = moe.moe_forward(p, x, cfg)

outs = {}
for dispatch in ("gather", "a2a"):
    c = dataclasses.replace(cfg, dispatch=dispatch)
    with shlib.use_mesh(mesh):
        y, aux = jax.jit(lambda p_, x_: moe.moe_forward(p_, x_, c))(p, x)
    outs[dispatch] = (np.asarray(y), float(aux))

np.testing.assert_allclose(outs["gather"][0], np.asarray(y_ref), rtol=2e-4,
                           atol=2e-4)
# a2a path recomputes routing per seq-shard: capacity boundaries differ from
# the global dispatch, so allow small drop-induced deviation on few tokens
diff = np.abs(outs["a2a"][0] - np.asarray(y_ref))
frac_close = (diff < 1e-3).mean()
assert frac_close > 0.95, f"a2a path diverges: {frac_close:.2%} close"
# gradient flows through both shard_map paths
for dispatch in ("gather", "a2a"):
    c = dataclasses.replace(cfg, dispatch=dispatch)
    with shlib.use_mesh(mesh):
        g = jax.jit(jax.grad(lambda x_: moe.moe_forward(p, x_, c)[0].sum()))(x)
    assert np.isfinite(np.asarray(g)).all()
print("MOE_DISPATCH_OK")
"""


@pytest.mark.slow  # 8-fake-device subprocess, fwd+bwd compiles
def test_moe_dispatch_equivalence():
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=500)
    assert "MOE_DISPATCH_OK" in r.stdout, r.stdout + r.stderr[-3000:]
