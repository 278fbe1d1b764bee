"""Distribution-policy walkthrough (runs on CPU with 8 fake devices via
a subprocess-style guard): shows the logical-axis sharding rules, the
GPipe pipeline over a pod axis, int8+EF gradient compression, and an
elastic down-scale replan — the 1000-node toolkit in miniature.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python examples/multipod_policy.py
"""

import os

if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compile_cache import enable_compile_cache
from repro.dist import compress, elastic, pipeline
from repro.dist.sharding import ShardingPolicy, param_specs, policy_for_mesh

enable_compile_cache()

# --- 1. mesh + policy ------------------------------------------------------
devs = np.asarray(jax.devices()).reshape(2, 2, 2)
mesh = Mesh(devs, ("pod", "data", "model"))
policy = policy_for_mesh(mesh, fsdp=True)
print("mesh:", dict(mesh.shape))
print("activation rules:", policy.rules())

# --- 2. parameter sharding by role ----------------------------------------
params = {"embed": jnp.zeros((64, 16)),
          "attn": {"wq": jnp.zeros((16, 4, 8)), "wk": jnp.zeros((16, 2, 8)),
                   "wo": jnp.zeros((4, 8, 16))},
          "moe": {"w_gate": jnp.zeros((4, 16, 32))}}
specs = param_specs(params, mesh, policy)
for k, v in jax.tree_util.tree_leaves_with_path(
        specs, is_leaf=lambda x: isinstance(x, P)):
    print(" ", jax.tree_util.keystr(k), "→", v)

# --- 3. pipeline over the pod axis ----------------------------------------
pmesh = Mesh(devs.reshape(8)[:2], ("pod",))
stage = lambda p, x: jnp.tanh(x @ p)
stacked = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8)) * 0.5
xs = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 8))
floss = pipeline.gpipe_spmd(stage, pmesh, loss_fn=lambda a: jnp.sum(a ** 2))
with pmesh:
    loss = float(floss(stacked, xs))
print(f"gpipe over pod axis: loss={loss:.3f}, "
      f"bubble={pipeline.bubble_fraction(2, 4):.0%}")

# --- 4. int8 + error-feedback cross-pod reduction ---------------------------
x = jnp.stack([jnp.full((8,), 1.0), jnp.full((8,), 3.0)])
f = jax.shard_map(lambda v: compress.cross_pod_mean_int8(v[0])[None],
                  mesh=pmesh, in_specs=P("pod"), out_specs=P("pod"))
with pmesh:
    out = f(x)
print("cross-pod int8 mean of (1, 3):", float(out[0, 0]),
      "(4x fewer bytes over the slow link)")

# --- 5. elastic down-scale plan ---------------------------------------------
plan = elastic.plan_downsize({"data": 16, "model": 16}, dead_fraction=0.2)
print(f"elastic: lose 20% of chips → mesh {plan.old_shape} → "
      f"{plan.new_shape} (TP preserved, {plan.dropped_rows} DP rows "
      f"dropped; checkpoint restores reshard automatically)")
