"""Mesh training: shard the megastep training path across devices.

Trains the quickstart Tree-LSTM data-parallel over a {"data": R} mesh:
``compose_sharded`` splits each composed batch into node-balanced
per-replica sub-batches, ``ShardedPipeline`` packs one LevelSchedule
per replica, and ``Trainer(dp_shard=True)`` runs the megastep under
``shard_map`` with int8 + error-feedback gradient all-reduce.

Run (8 fake host devices on a single CPU):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python examples/train_mesh.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.scheduler import execute, readout_roots
from repro.core.structure import random_binary_tree
from repro.dist.elastic import plan_downsize, remesh
from repro.models.treelstm import TreeLSTMVertex
from repro.pipeline import ShardedPipeline
from repro.train import MetricLogger, TrainConfig, Trainer

enable_compile_cache()

IN_DIM, HIDDEN = 16, 8

# --- 1. a mesh over whatever devices exist (forced-host CPUs count) ------
R = len(jax.devices())
mesh = remesh(jax.devices(), {"data": R})
print(f"mesh: {R} devices on axis 'data'")

# --- 2. vertex function + a ragged tree corpus ---------------------------
fn = TreeLSTMVertex(input_dim=IN_DIM, hidden=HIDDEN, arity=2)
rng = np.random.default_rng(0)
graphs = [random_binary_tree(int(rng.integers(2, 24)), rng)
          for _ in range(128)]
inputs = [rng.standard_normal((g.num_nodes, IN_DIM)).astype(np.float32)
          * 0.3 for g in graphs]
targets = rng.standard_normal((128, HIDDEN)).astype(np.float32) * 0.1


# --- 3. the dp_shard loss contract: weighted SUM, not mean ---------------
# Each replica returns sum(per_sample * weights); the trainer psums the
# sums and weights across the mesh, so zero-weight filler samples (short
# final batches) drop out exactly and the global loss matches the
# single-replica baseline to fp roundoff.
def loss_fn(params, batch):
    buf = execute(fn, params, batch["dev"], batch["ext"],
                  fusion_mode="auto").buf
    root_h = readout_roots(buf, batch["dev"])[:, HIDDEN:]
    per = jnp.mean((root_h - batch["target"]) ** 2, axis=-1)
    return jnp.sum(per * batch["weights"]), {}


# --- 4. shard-aware pipeline + trainer -----------------------------------
pipe = ShardedPipeline(ext_dim=IN_DIM, num_shards=R)
tr = Trainer(loss_fn, lambda k: fn.init(k),
             TrainConfig(lr=3e-3, warmup_steps=4, total_steps=24,
                         weight_decay=0.0, log_every=4,
                         dp_shard=True,          # shard_map over "data"
                         compress_grads=True),   # int8 + error feedback
             mesh=mesh)
state = tr.init_state(jax.random.PRNGKey(0))


def epochs():
    while True:
        yield (graphs, inputs, {"target": list(targets)})


state, logger = tr.fit(state, epochs(), steps=24,
                       compose=pipe.composer(batch_size=32),
                       pipeline=pipe, logger=MetricLogger())
print(f"trained to step {int(np.asarray(state.step))}; "
      f"EF residual live: "
      f"{sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(state.ef)):.2e}")
print(f"per-replica cache stats: {pipe.stats()}")

# --- 5. elastic shrink: lose half the mesh, keep training ----------------
# plan_downsize snaps the surviving count to a power of two (integer
# arithmetic — no float-rounding a replica away); with ckpt_dir set,
# maybe_restore on a new Trainer at the smaller R resumes from the last
# checkpoint (see tests/test_dist_shard.py for the full 8->4 path).
plan = plan_downsize({"data": R}, dead_fraction=0.5)
print(f"elastic plan after losing half the mesh: {plan.new_shape}")
