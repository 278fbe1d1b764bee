"""Tree-LSTM sentiment classifier on SST-like data (paper §5 model (d)).

End-to-end: dataset → batch composer → schedule pipeline
(topology-fingerprint cache + shape buckets + async packing) → batched
scheduling of F over G → classification head on root states → AdamW —
the paper's flagship dynamic-NN workload, trained for a few hundred
steps on CPU on the production host path.  Labels ride through the
composer's reordering as aux riders, aligned with their samples.

Note on what composition buys HERE: SST-like random binary parses are
nearly all distinct topologies, so there are no same-fingerprint
groups to batch within an epoch — the composer's wins on this corpus
are depth-sorted bucket occupancy and deterministic epoch replay
(from epoch 2 on, every batch is a schedule-cache hit).  On skewed
corpora (repeated shapes — chains, serving traffic) it additionally
manufactures WITHIN-epoch hits; `tests/test_composer.py`
(`test_composed_epoch_beats_fifo_on_skewed_corpus`) checks that case.

Run:  PYTHONPATH=src python examples/treelstm_sentiment.py [--steps 150]
      (--no-compose falls back to FIFO epoch slicing)
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.scheduler import execute_lazy, readout_roots
from repro.data import ComposedBatchSource, sst_like_dataset
from repro.models.treelstm import TreeLSTMVertex
from repro.optim import adamw_init, adamw_update, warmup_cosine
from repro.pipeline import BucketPolicy, SchedulePipeline


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--no-compose", action="store_true",
                    help="FIFO epoch slicing instead of the composer")
    args = ap.parse_args()

    input_dim = 32
    ds = sst_like_dataset(512, input_dim=input_dim, seed=0)
    fn = TreeLSTMVertex(input_dim=input_dim, hidden=args.hidden, arity=2)

    # The production host path: fingerprint → LRU schedule cache →
    # bucketed pads (few compiled programs) → pack, prefetched on a
    # background thread so the device never waits on packing.
    pipe = SchedulePipeline(input_dim,
                            bucket_policy=BucketPolicy(mode="pow2"))
    rng_np = np.random.default_rng(0)

    key = jax.random.PRNGKey(0)
    params = {
        "cell": fn.init(key),
        "head": jax.random.normal(jax.random.PRNGKey(1),
                                  (args.hidden, 2)) * 0.1,
    }
    opt = adamw_init(params)
    sched_fn = warmup_cosine(3e-3, 20, args.steps)

    def fifo_batches():
        # Epoch-cycled fixed partition: from epoch 2 on, every batch
        # topology has been seen — the schedule cache serves them all.
        order = rng_np.permutation(len(ds))
        parts = [order[i: i + args.batch]
                 for i in range(0, len(ds) - args.batch + 1, args.batch)]
        while True:
            for idx in parts:
                graphs, inputs, labels = ds.batch(idx)
                yield graphs, inputs, {"labels": labels}

    def composed_batches():
        # Pipeline-aware batch formation: any same-fingerprint samples
        # are grouped into whole batches, leftovers fill buckets by
        # depth (occupancy), and the deterministic plan replays every
        # epoch (cache hits from epoch 2 on).  Labels ride through the
        # reordering as aux.
        return ComposedBatchSource(
            ds.graphs, ds.inputs, {"labels": list(ds.labels)},
            composer=pipe.composer(args.batch))

    @jax.jit
    def train_step(params, opt, ext, labels, dev):
        def loss_fn(p):
            buf = execute_lazy(fn, p["cell"], ext, dev)
            root_h = readout_roots(buf, dev)[:, args.hidden:]
            logits = root_h @ p["head"]
            lse = jax.scipy.special.logsumexp(logits, -1)
            nll = lse - jnp.take_along_axis(
                logits, labels[:, None], 1)[:, 0]
            acc = jnp.mean((jnp.argmax(logits, -1) == labels))
            return jnp.mean(nll), acc
        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt, _ = adamw_update(params, grads, opt,
                                      lr=sched_fn(opt.step),
                                      weight_decay=0.0)
        return params, opt, loss, acc

    source = fifo_batches() if args.no_compose else composed_batches()
    batches = pipe.prefetch(source, depth=2)
    try:
        for step in range(1, args.steps + 1):
            b = next(batches)
            labels = jnp.asarray(np.asarray(b.aux["labels"]))
            params, opt, loss, acc = train_step(params, opt, b.ext,
                                                labels, b.dev)
            if step % 25 == 0 or step == 1:
                print(f"step {step:4d}  loss {float(loss):.4f}  "
                      f"acc {float(acc):.2f}")
    finally:
        batches.close()
    s = pipe.stats()
    print(f"done — schedule pipeline: {s['hit_rate']:.0%} cache hit rate, "
          f"{s['compiled_shapes']} compiled shape(s) over {s['batches']} "
          f"batches (async-packed; zero re-tracing on hits; "
          f"{s['packs']} cold packs)")
    if not args.no_compose and getattr(source, "stats", None) is not None:
        cs = source.stats
        print(f"composer: {cs.num_groups} topology groups → "
              f"{cs.group_batches} whole-group + {cs.leftover_batches} "
              f"leftover batches/epoch, predicted epoch-1 hit rate "
              f"{cs.hit_rate:.0%}, mean occupancy {cs.mean_occupancy:.0%}")


if __name__ == "__main__":
    main()
