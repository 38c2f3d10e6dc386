"""Pipeline parallelism (GPipe over a mesh axis): numeric validation against
the sequential oracle.  shard_map needs multiple devices, so the check runs
in a subprocess with forced host devices (the only test allowed to do so —
the flag must never leak into this process)."""

import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.sharding.pipeline import gpipe, reference_forward

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
S, L, D = 4, 8, 16          # 4 stages x 2 layers
n_micro, mb, seq = 6, 4, 8

rng = np.random.default_rng(0)
params = {"w": jnp.asarray(rng.normal(size=(L, D, D)) * 0.3, jnp.float32),
          "b": jnp.asarray(rng.normal(size=(L, D)) * 0.1, jnp.float32)}
x = jnp.asarray(rng.normal(size=(n_micro, mb, seq, D)), jnp.float32)

def stage_fn(p, x):
    def body(h, lp):
        return jnp.tanh(h @ lp[0] + lp[1]), None
    h, _ = jax.lax.scan(body, x, (p["w"], p["b"]))
    return h

pipelined = gpipe(stage_fn, mesh, stage_axis="model", data_axes=("data",))
with jax.set_mesh(mesh):
    got = jax.jit(pipelined)(params, x)
want = reference_forward(stage_fn, params, x, n_stages=4)
err = float(jnp.abs(got - want).max())
assert err < 1e-5, err

# differentiability: grad of a scalar loss through the pipeline
def loss(p):
    return jnp.sum(jax.jit(pipelined)(p, x) ** 2)
with jax.set_mesh(mesh):
    g = jax.grad(loss)(params)
def loss_ref(p):
    return jnp.sum(reference_forward(stage_fn, p, x, 4) ** 2)
g_ref = jax.grad(loss_ref)(params)
gerr = max(float(jnp.abs(a - b).max())
           for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)))
assert gerr < 1e-3, gerr
print(f"PIPELINE_OK fwd_err={err:.2e} grad_err={gerr:.2e}")
"""


def test_gpipe_matches_sequential_and_is_differentiable():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert "PIPELINE_OK" in out.stdout, (out.stdout[-2000:],
                                         out.stderr[-2000:])
