"""Training loop: ``Trainer.fit`` fed by the batch composer and the
``SchedulePipeline``, forward and backward through the fused megasteps.

Set-up (all of it counts in ``setup_s``): the corpus, its inputs and
regression targets from the seed on the host; the weights from the
seed on the device; one epoch composed and its schedules packed into
the pipeline's cache (the window then runs the cache's steady state);
one step on a spare copy of the state for every batch shape the epoch
holds; and the first three steps of the real state through the
window's own feed, which the reference checks afterwards.

Window: ``Trainer.fit`` in chunks of ``log_every`` steps until
``--seconds`` have passed; every step ends in a device sync (the
trainer reads its non-finite guard).  ``train_vertices_per_s`` is the
real vertices of every step in the window over the window's seconds.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import device
import costs
import generate
from reference import graph as refgraph
from tracing import TracedWindow, freeze_heap as _freeze_heap

#: Length of the traced part of a ``--trace 1`` window, in seconds.
TRACE_SECONDS = 2.0


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def _batch_dict(pb) -> dict:
    """A packed batch as the trainer's step takes it."""
    batch = {"dev": pb.dev, "ext": pb.ext}
    for name, vals in pb.aux.items():
        batch[name] = np.asarray(vals)
    return batch


def _leaf_norms(tree) -> dict:
    return {k: float(jnp.linalg.norm(v.astype(jnp.float32)))
            for k, v in tree.items()}


class Corpus:
    def __init__(self, widths: dict, traffic: dict, seed: int):
        rng = np.random.default_rng(seed)
        self.structs = generate.corpus(traffic, traffic["corpus_size"], rng)
        self.sizes = np.array([len(s) for s in self.structs], np.int64)
        self.inputs = generate.inputs(self.sizes, widths["input_dim"], rng,
                                      traffic["input_scale"])
        self.targets = generate.targets(len(self.structs), widths["hidden"],
                                        rng, traffic["target_scale"])
        self._counts = {}

    def level_counts(self, ids):
        """Per level of one batch: real vertices, edges, vertices with
        children (level ``t`` of every structure shares a launch)."""
        acc = {}
        for i in ids:
            i = int(i)
            if i not in self._counts:
                self._counts[i] = costs.level_counts([self.structs[i]])
            for t, c in enumerate(self._counts[i]):
                a = acc.setdefault(t, [0, 0, 0])
                for j in range(3):
                    a[j] += c[j]
        return [tuple(acc[t]) for t in sorted(acc)]


def run(ctx) -> dict:
    from repro.core.scheduler import execute, readout_roots
    from repro.core.structure import InputGraph
    from repro.pipeline import BucketPolicy, SchedulePipeline
    from repro.train import MetricLogger, Trainer, TrainConfig

    cfg, tr = ctx.cfg, ctx.traffic
    widths = cfg["vertex_args"]
    H, X = widths["hidden"], widths["input_dim"]
    opt = tr["optimizer"]
    corpus = Corpus(widths, tr, ctx.seed)
    graphs = [InputGraph(children=s) for s in corpus.structs]
    aux = {"target": list(corpus.targets)}

    key = jax.random.PRNGKey(generate.seed_key(ctx.seed))
    init = jax.jit(lambda k: ctx.reference.init(k, cfg))
    params0 = init(key)
    params_host = jax.tree.map(np.asarray, params0)
    vertex = ctx.vertex()

    def loss_fn(p, b):
        buf = execute(vertex, p, b["dev"], b["ext"],
                      fusion_mode="megastep").buf
        h = readout_roots(buf, b["dev"])[:, -H:]
        return jnp.mean(jnp.mean((h - b["target"]) ** 2, axis=-1)), {}

    tcfg = TrainConfig(lr=opt["lr"], warmup_steps=opt["warmup_steps"],
                       total_steps=opt["total_steps"], b1=opt["b1"],
                       b2=opt["b2"], weight_decay=opt["weight_decay"],
                       max_grad_norm=opt["max_grad_norm"],
                       log_every=tr["log_every"])
    trainer = Trainer(loss_fn, lambda _k: params0, tcfg)
    state = trainer.init_state(key)
    pipe = SchedulePipeline(ext_dim=X,
                            bucket_policy=BucketPolicy(mode=tr["buckets"]),
                            cache_capacity=tr["cache_capacity"])
    composer = pipe.composer(tr["batch"])
    quiet = lambda *_: None  # noqa: E731

    # One epoch composed and packed: the cache's steady state.
    with _annotate("bench.pipeline_pack"):
        epoch, _ = composer.compose(graphs, corpus.inputs, aux)
        for cb in epoch:
            pipe.cache.get_or_pack_device(cb.graphs, cb.pads,
                                          with_runs=pipe.with_runs)
    # Every batch shape of the epoch, once, on a spare copy of the state.
    shapes = {}
    for cb in epoch:
        shapes.setdefault((tuple(cb.pads), len(cb)), cb)
    warm = [_batch_dict(pipe.pack(*cb.as_item())) for cb in shapes.values()]
    spare = jax.tree.map(jnp.copy, state)
    spare, _ = trainer.fit(spare, iter(warm), steps=len(warm),
                           logger=MetricLogger(log_fn=quiet))
    jax.block_until_ready(spare)
    del spare, warm

    # The window's feed: epochs of the corpus, composed and packed on
    # the pipeline's background thread, as a training job runs.
    record = []

    def epochs():
        while True:
            yield graphs, corpus.inputs, aux

    def items():
        for g, x, a in epochs():
            batches, _ = composer.compose(g, x, a)
            for cb in batches:
                yield cb.as_item()

    def feed():
        packer = pipe.prefetch(items(), depth=2)
        try:
            for pb in packer:
                b = _batch_dict(pb)
                record.append((np.asarray(b["sample_ids"]),
                               pb.dev.T, pb.dev.M))
                yield b
        finally:
            packer.close()

    stream = feed()
    logger = MetricLogger(log_fn=quiet)
    losses, grad1, delta = [], None, None
    for s in (1, 2, 3):
        with _annotate("bench.train_step"):
            state, _ = trainer.fit(state, stream, steps=s, logger=logger)
        losses.append(float(logger.history[-1]["loss"]))
        if s == 1:
            # The optimizer's first moment after one step is
            # (1 - b1) times the clipped gradient it was given.
            grad1 = _leaf_norms(jax.tree.map(
                lambda m: m / (1.0 - opt["b1"]), state.opt.mu))
    delta = _leaf_norms(jax.tree.map(
        lambda p, p0: p - p0, state.params, params_host))
    first_ids = [r[0] for r in record[:3]]

    # ``fit`` logs at the end of every call, so the window calls it once
    # per ``log_every`` steps: the loop logs as often as a job would.
    chunk = tr["log_every"]
    window = min(ctx.seconds, TRACE_SECONDS) if ctx.trace else ctx.seconds
    done, n0 = 3, len(record)
    compiles0 = ctx.compiles.count
    _freeze_heap()
    traced = TracedWindow(ctx.trace_dir()) if ctx.trace else None
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    while True:
        with _annotate("bench.train_step"):
            state, _ = trainer.fit(state, stream, steps=done + chunk,
                                   logger=logger)
        done += chunk
        elapsed = time.perf_counter() - t0
        if elapsed >= window:
            break
    if traced:
        traced.stop()
    compiles = ctx.compiles.count - compiles0
    gc.unfreeze()
    stream.close()
    steps = record[n0:]
    vertices = int(sum(corpus.sizes[ids].sum() for ids, _T, _M in steps))
    slots = int(sum(T * M for _ids, T, M in steps))
    skipped = int(logger.counters.get("nonfinite_skips", 0))
    memory_peak = device.peak_bytes(ctx.cell["chips"])

    rec = {"kind": cfg["kind"], "hidden": H, "input_dim": X,
           "chips": ctx.cell["chips"], "window_s": elapsed,
           "steps": len(steps), "vertices": vertices, "slots": slots}
    if traced:
        rec.update(traced.record())
        rec["step_levels"] = [corpus.level_counts(ids)
                              for ids, _T, _M in steps]
        rec["device_kind"] = jax.devices()[0].device_kind

    # Free the program's state, then run the reference.
    del state, trainer, pipe, stream, composer, epoch
    gc.collect()
    batches = []
    for ids in first_ids:
        pl = refgraph.plan([corpus.structs[i] for i in ids],
                           widths.get("arity", 1))
        x = refgraph.block_inputs([corpus.inputs[i] for i in ids], pl)
        batches.append((pl, x, corpus.targets[ids]))
    ref = refgraph.train_steps(ctx.reference, params_host, batches, opt)
    ref_norms = {"losses": ref["losses"],
                 "grad1": _leaf_norms(ref["grad1"]),
                 "delta": _leaf_norms(ref["delta"])}
    values = check.train_gaps(losses, grad1, delta, ref_norms)
    values["batch_shortfall"] = check.batch_shortfall(
        first_ids, tr["batch"], tr["corpus_size"])
    control = None
    if ctx.control:
        ctl = refgraph.train_steps(ctx.reference, params_host, batches, opt,
                                   how=ctx.control_how())
        control = check.train_gaps(ctl["losses"], _leaf_norms(ctl["grad1"]),
                                   _leaf_norms(ctl["delta"]), ref_norms)

    notes = [f"train steps={len(steps)} window_s={elapsed!r} "
             f"vertices={vertices} slots={slots} "
             f"compiles_in_window={compiles} nonfinite_skips={skipped}",
             f"train losses={losses} ref_losses={ref['losses']}"]
    return {"metrics": {"train_vertices_per_s": vertices / elapsed,
                        "setup_s": setup_s},
            "values": values, "sound": skipped == 0,
            "attempted": len(steps), "failed": skipped,
            "memory_peak_bytes": memory_peak, "record": rec,
            "notes": notes, "control_values": control}

