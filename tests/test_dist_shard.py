"""The sharded megastep training path (tier1-dist suite).

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` via
fresh-interpreter subprocesses (jax locks the device count at backend
init).  Asserts the ISSUE's acceptance bars on a real 8-replica host
mesh:

  (a) sharded epoch/step losses match the single-replica composed
      baseline to fp roundoff, per-sample losses realigned by
      ``sample_ids``;
  (b) each replica's PRE-reduction gradients are bit-identical to a
      solo ``SchedulePipeline`` pack of that replica's sub-batch — the
      stacked ``DeviceSchedule`` + ``shard_map`` machinery adds zero
      numerical noise;
  (c/d) covered host-side in ``test_composer.py`` /
      ``test_pipeline.py`` (node balance, per-replica epoch-2 cache
      hit rate) — no mesh needed;
  EF + elastic: ``compress_grads=True`` carries a live per-replica
      residual in ``TrainState.ef``, and a ``plan_downsize``-driven
      8→4 restart restores from checkpoint and keeps training.

``REPRO_FUSION`` is inherited by the subprocesses, so the tier1-dist
CI job sweeps the fused and unfused legs with the same tests.
"""

import pytest

from tests.util_subproc import run_with_devices

_PRELUDE = """
import os, numpy as np, jax, jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core.scheduler import execute, readout_roots
from repro.core.structure import random_binary_tree
from repro.dist.elastic import plan_downsize, remesh
from repro.models.treelstm import TreeLSTMVertex
from repro.pipeline import SchedulePipeline, ShardedPipeline
from repro.train import MetricLogger, TrainConfig, Trainer

FUSION = os.environ.get("REPRO_FUSION", "auto")
IN_DIM, HID = 8, 4
fn = TreeLSTMVertex(input_dim=IN_DIM, hidden=HID, arity=2)

rng = np.random.default_rng(0)
graphs = [random_binary_tree(int(rng.integers(2, 14)), rng)
          for _ in range(64)]
inputs = [rng.standard_normal((g.num_nodes, IN_DIM)).astype(np.float32)
          * 0.3 for g in graphs]
targets = rng.standard_normal((64, HID)).astype(np.float32) * 0.1


def per_sample(params, dev, ext, tgt):
    buf = execute(fn, params, dev, ext, fusion_mode=FUSION).buf
    root_h = readout_roots(buf, dev)[:, HID:]
    return jnp.mean((root_h - tgt) ** 2, axis=-1)


def sharded_loss(params, batch):
    per = per_sample(params, batch["dev"], batch["ext"], batch["target"])
    w = batch["weights"]
    return jnp.sum(per * w), {}


def solo_loss(params, batch):
    per = per_sample(params, batch["dev"], batch["ext"], batch["target"])
    return jnp.mean(per), {}


def epochs_of(n):
    for _ in range(n):
        yield (graphs, inputs, {"target": list(targets)})
"""


@pytest.mark.slow
def test_sharded_step_matches_single_replica_baseline():
    """Criteria (a) + (b) in one interpreter: trainer-level loss
    parity over 2 composed epochs, then per-replica bit-identity of
    pre-reduction grads and per-sample losses against solo packs."""
    run_with_devices(_PRELUDE + """
R, BS, STEPS = 8, 16, 8
mesh = remesh(jax.devices(), {"data": R})

def run_sharded():
    pipe = ShardedPipeline(IN_DIM, R)
    tr = Trainer(sharded_loss, lambda k: fn.init(k),
                 TrainConfig(lr=1e-2, warmup_steps=2, total_steps=STEPS,
                             weight_decay=0.0, log_every=1,
                             dp_shard=True),
                 mesh=mesh)
    state = tr.init_state(jax.random.PRNGKey(0))
    logger = MetricLogger(log_fn=lambda *_: None)
    state, logger = tr.fit(state, epochs_of(3), steps=STEPS,
                           compose=pipe.composer(BS), pipeline=pipe,
                           logger=logger)
    return state, [h["loss"] for h in logger.history]

def run_solo():
    pipe = SchedulePipeline(IN_DIM)
    tr = Trainer(solo_loss, lambda k: fn.init(k),
                 TrainConfig(lr=1e-2, warmup_steps=2, total_steps=STEPS,
                             weight_decay=0.0, log_every=1))
    state = tr.init_state(jax.random.PRNGKey(0))
    logger = MetricLogger(log_fn=lambda *_: None)
    state, logger = tr.fit(state, epochs_of(3), steps=STEPS,
                           compose=pipe.composer(BS), pipeline=pipe,
                           logger=logger)
    return state, [h["loss"] for h in logger.history]

(s_sh, loss_sh), (s_solo, loss_solo) = run_sharded(), run_solo()
assert len(loss_sh) == STEPS and len(loss_solo) == STEPS
np.testing.assert_allclose(loss_sh, loss_solo, rtol=1e-5, atol=1e-7)
jax.tree.map(lambda a, b: np.testing.assert_allclose(
    np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
    s_sh.params, s_solo.params)
print("loss parity OK")

# --- (b) bit-identity: per-replica pre-reduction grads vs solo packs
params = fn.init(jax.random.PRNGKey(1))
pipe = ShardedPipeline(IN_DIM, R)
steps, _ = pipe.composer(BS).compose_sharded(
    graphs, inputs, {"target": list(targets)}, num_shards=R)
st = steps[0]
batch = pipe.pack_step(st)
batch = {k: jax.tree.map(jnp.asarray, v) for k, v in batch.items()}

def local_sum_and_per(p, local):
    per = per_sample(p, local["dev"], local["ext"], local["target"])
    return jnp.sum(per * local["weights"]), per

@partial(jax.shard_map, mesh=mesh, in_specs=(P(), P("data")),
         out_specs=(P("data"), P("data")), check_vma=False)
def per_replica(p, b):
    local = jax.tree.map(lambda a: a[0], b)
    (s, per), g = jax.value_and_grad(
        lambda q: local_sum_and_per(q, local), has_aux=True)(p)
    return per[None], jax.tree.map(lambda x: x[None], g)

with mesh:
    per_sh, g_sh = jax.jit(per_replica)(params, batch)

solo_pipe = SchedulePipeline(IN_DIM)
for r, rep in enumerate(st.replicas):
    pb = solo_pipe.pack(rep.graphs, rep.inputs, pads=st.pads)
    tgt = jnp.asarray(np.stack([np.asarray(t)
                                for t in rep.aux["target"]]))
    w = jnp.asarray(rep.aux["weights"], jnp.float32)
    solo = jax.jit(lambda p, d, e, t, w: jax.value_and_grad(
        lambda q: (lambda per: (jnp.sum(per * w),
                                per))(per_sample(q, d, e, t)),
        has_aux=True)(p))
    (s_r, per_r), g_r = solo(params, pb.dev, pb.ext, tgt, w)
    np.testing.assert_array_equal(np.asarray(per_r),
                                  np.asarray(per_sh[r]))
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(g_r)[0],
            jax.tree_util.tree_flatten_with_path(
                jax.tree.map(lambda x: x[r], g_sh))[0]):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (r, ka)
print("bit-identity OK")

# per-sample parity vs the single-replica UNION packing, by sample id
union_g = [g for rep in st.replicas for g in rep.graphs]
union_x = [x for rep in st.replicas for x in rep.inputs]
union_t = jnp.asarray(np.stack([np.asarray(t) for rep in st.replicas
                                for t in rep.aux["target"]]))
upb = solo_pipe.pack(union_g, union_x, pads=st.pads)
per_union = jax.jit(per_sample)(params, upb.dev, upb.ext, union_t)
np.testing.assert_allclose(np.asarray(per_sh).ravel(),
                           np.asarray(per_union), rtol=1e-6, atol=1e-8)
print("per-sample parity OK")
""", n_devices=8)


@pytest.mark.slow
def test_ef_on_mesh_and_elastic_8_to_4_restart():
    """compress_grads on the mesh carries a live per-replica residual
    in TrainState.ef, and a plan_downsize-driven 8→4 restart restores
    the checkpoint onto the smaller mesh and keeps training."""
    run_with_devices(_PRELUDE + """
import tempfile
ckpt_dir = tempfile.mkdtemp()
R, BS = 8, 16

mesh = remesh(jax.devices(), {"data": R})
pipe = ShardedPipeline(IN_DIM, R)
tr = Trainer(sharded_loss, lambda k: fn.init(k),
             TrainConfig(lr=1e-2, warmup_steps=2, total_steps=16,
                         weight_decay=0.0, log_every=1, dp_shard=True,
                         compress_grads=True, ckpt_dir=ckpt_dir,
                         ckpt_every=4),
             mesh=mesh)
state = tr.init_state(jax.random.PRNGKey(0))
logger = MetricLogger(log_fn=lambda *_: None)
state, logger = tr.fit(state, epochs_of(10), steps=8,
                       compose=pipe.composer(BS), pipeline=pipe,
                       logger=logger)
assert state.ef is not None, "EF residual missing from TrainState"
ef_leaves = jax.tree.leaves(state.ef)
assert all(l.shape[0] == R for l in ef_leaves)
ef_mass = sum(float(jnp.sum(jnp.abs(l))) for l in ef_leaves)
assert ef_mass > 0, "EF residual never updated — compression not EF"
print("EF on mesh OK, |ef| =", ef_mass)
saved_w = {k: np.asarray(v) for k, v in
           jax.tree_util.tree_flatten_with_path(state.params)[0]}

# --- simulated loss of half the replicas -> shrink and resume
plan = plan_downsize({"data": R}, dead_fraction=0.5)
assert plan.new_shape == {"data": 4}
mesh2 = remesh(jax.devices()[:4], plan.new_shape)
pipe2 = ShardedPipeline(IN_DIM, 4)
tr2 = Trainer(sharded_loss, lambda k: fn.init(k),
              TrainConfig(lr=1e-2, warmup_steps=2, total_steps=16,
                          weight_decay=0.0, log_every=1, dp_shard=True,
                          compress_grads=True, ckpt_dir=ckpt_dir,
                          ckpt_every=4),
              mesh=mesh2)
state2 = tr2.init_state(jax.random.PRNGKey(7))
state2, start = tr2.maybe_restore(state2)
assert start == 8, start
for (k, a) in jax.tree_util.tree_flatten_with_path(state2.params)[0]:
    assert np.array_equal(np.asarray(a), saved_w[k]), k
assert state2.ef is not None
assert all(l.shape[0] == 4 for l in jax.tree.leaves(state2.ef))
assert all(float(jnp.sum(jnp.abs(l))) == 0.0
           for l in jax.tree.leaves(state2.ef))   # cold EF after restore

logger2 = MetricLogger(log_fn=lambda *_: None)
state2, logger2 = tr2.fit(state2, epochs_of(10), steps=16,
                          compose=pipe2.composer(BS), pipeline=pipe2,
                          logger=logger2)
assert int(np.asarray(state2.step)) == 16
losses = [h["loss"] for h in logger2.history]
assert all(np.isfinite(losses)), losses
assert losses[-1] < logger.history[0]["loss"], (
    "training did not keep converging after the elastic restart")
print("elastic 8->4 restart OK, losses", losses[:2], "->", losses[-1])
""", n_devices=8)
