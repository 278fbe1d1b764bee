"""Quickstart: the Cavs vertex-centric API in ~30 lines of user code.

Declares an N-ary child-sum Tree-LSTM as a vertex function F (the
paper's Fig. 4), packs a batch of random parse trees G, and runs one
batched training step — no per-sample graph construction anywhere.

Run:  PYTHONPATH=src python examples/quickstart.py

With ``REPRO_TRACE=trace.json`` in the environment the same run also
writes a Chrome/Perfetto timeline (open in ui.perfetto.dev): compose →
pack → cache-hit → H2D → fwd/bwd → reduce spans, correlated by batch
and step ids.  Tracing off costs nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.scheduler import execute_lazy, readout_roots
from repro.core.structure import random_binary_tree
from repro.models.treelstm import TreeLSTMVertex
from repro.obs import trace
from repro.pipeline import SchedulePipeline

enable_compile_cache()

# --- 1. declare F once (the static vertex function) ----------------------
fn = TreeLSTMVertex(input_dim=32, hidden=64, arity=2)
params = fn.init(jax.random.PRNGKey(0))

# --- 2. per-sample input graphs G arrive as DATA (read "through I/O") ----
rng = np.random.default_rng(0)
graphs = [random_binary_tree(int(rng.integers(4, 20)), rng) for _ in range(8)]
inputs = [rng.standard_normal((g.num_nodes, 32)).astype(np.float32) * 0.1
          for g in graphs]

# --- 3. the schedule pipeline packs the minibatch (host-side, NumPy): ----
# topology-fingerprint cache + shape buckets, so repeated topologies
# skip packing and near-miss batches reuse one compiled program.
pipe = SchedulePipeline(ext_dim=32)
batch = pipe.pack(graphs, inputs)
print(f"packed {len(graphs)} trees: {batch.sched.T} levels × "
      f"{batch.sched.M} slots, occupancy {batch.sched.occupancy:.0%}")

# --- 4. batched training step: schedule F over G, lazy-batched grads -----
@jax.jit
def fwd_bwd(p, e, dev):
    def loss(pp):
        buf = execute_lazy(fn, pp, e, dev)        # Alg. 1 + §3.5 lazy
        root_h = readout_roots(buf, dev)[:, 64:]  # [K, hidden]
        return jnp.mean(root_h ** 2)
    return jax.value_and_grad(loss)(p)


@jax.jit
def apply_grads(p, g):
    return jax.tree.map(lambda w, gw: w - 0.1 * gw, p, g)


def train_step(p, e, dev, step):
    # Under REPRO_TRACE each step is a train.step span with nested
    # fwd/bwd and reduce children, which time the dispatch, and a
    # train.sync child, which times the step's wait for the device.
    # Spans never wait themselves.  With no tracer the span sites are a
    # single is-None check each.
    with trace.correlate(step=step), trace.span("train.step", step=step):
        with trace.span("train.fwd_bwd"):
            l, g = fwd_bwd(p, e, dev)
        with trace.span("train.reduce"):
            p = apply_grads(p, g)
        with trace.span("train.sync"):
            jax.block_until_ready(p)
    return l, p

loss, params = train_step(params, batch.ext, batch.dev, step=0)
print(f"one batched step OK — loss {float(loss):.5f}")
print("the SAME compiled program serves any other batch of trees:")
graphs2 = [random_binary_tree(int(rng.integers(4, 20)), rng)
           for _ in range(8)]
inputs2 = [rng.standard_normal((g.num_nodes, 32)).astype(np.float32) * 0.1
           for g in graphs2]
batch2 = pipe.pack(graphs2, inputs2)       # same bucket → no re-compile
loss2, params = train_step(params, batch2.ext, batch2.dev, step=1)
print(f"second batch, zero graph-construction overhead — "
      f"loss {float(loss2):.5f}")
print(f"pipeline stats: {pipe.stats()}")

# --- 5. pipeline-aware batch formation: COMPOSE batches for cache hits ---
# A corpus with repeated topologies (the real-world case).  FIFO slicing
# interleaves them — distinct batch fingerprints, no hits; the composer
# groups same-fingerprint samples into whole batches, so every batch
# after a group's first is a schedule-cache hit.
corpus = [graphs[i % 4] for i in range(64)]          # heavy repetition
corpus_in = [inputs[i % 4] for i in range(64)]
composed, stats = pipe.compose(corpus, corpus_in, batch_size=8)
for cb in composed:
    pipe.pack(*cb.as_item())             # sample_ids ride in aux
print(f"composed {stats.num_batches} batches from {stats.num_groups} "
      f"topology groups: predicted hit rate {stats.hit_rate:.0%}, "
      f"measured {pipe.cache.hit_rate:.0%} overall, occupancy "
      f"{stats.mean_occupancy:.0%}")
print("(set REPRO_SCHED_PERSIST=<dir> and re-run: the warm restart "
      "packs zero schedules — they load from the on-disk store)")
