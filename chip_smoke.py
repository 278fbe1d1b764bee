#!/usr/bin/env python3
"""Chip smoke test: the fused vertex engine end to end on a TPU.

    python chip_smoke.py             # one chip: train + serve phases
    python chip_smoke.py --chips 4   # four chips: sharded training and
                                     # its single-chip baseline only

Run it from the repository root; it imports ``repro`` from ``src/``
beside this file.  It needs a TPU: when JAX finds none (for example
under ``JAX_PLATFORMS=cpu``) it exits non-zero and prints no result.

Phases (one chip, paper widths ``hidden=512, input_dim=256``, random
weights from ``--seed``):

  * ``train`` — ``Trainer`` + ``SchedulePipeline`` with the batch
    composer take 3 steps for ``tree_lstm`` (batch 64), ``var_lstm``
    (batch 64) and ``tree_fc`` (256-leaf trees, batch 16), forward and
    backward through the fused Pallas megasteps
    (``fusion_mode="megastep"``, which raises instead of falling back).
    The step-1 loss and parameter gradients are compared with the
    op-by-op ``fusion_mode="none"`` path under
    ``jax.default_matmul_precision("highest")`` on the same chip;
  * ``serve`` — a ``ContinuousBatchEngine`` with the Tree-LSTM cell
    answers 32 requests of random binary trees (2–54 leaves), twice
    (the first round compiles).  Every request must end ``ok`` with no
    degradation, and every root state must match scoring the request
    alone on the reference path.

``--chips 4`` runs only Tree-LSTM training data-parallel over a
``{"data": 4}`` mesh with int8 + error-feedback gradient sync, against
the single-replica composed baseline on one chip of the same process.

Each phase prints one line with its compile and run seconds and its
errors against the reference.  Before the last line the
``kernel.dispatch`` counters show which backend served each kernel op.
The last line is ``{"ok": true, "device": {...}}``; any failure exits
non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

HIDDEN, INPUT_DIM = 512, 256        # configs/paper.py widths
STEPS = 3
#: Relative tolerance of the fused path (default matmul precision)
#: against the reference (float32 "highest").  On the TPU a default
#: float32 matmul takes bfloat16 inputs (8-bit mantissa, relative
#: rounding 2^-9) with float32 accumulation; that error reaches the
#: loss and gradients through the hoisted projection, the recurrences
#: and the lazy parameter pass.  The kernels' math is float32.
TOL = 3e-2
#: Sharded vs single-replica per-step loss: the int8 + error-feedback
#: sync quantizes each gradient tensor to 1/127 of its max per step,
#: and the replicas' smaller batches round differently.
TOL_SHARDED = 1e-2

TRAIN_CELLS = (("tree_lstm", 64), ("var_lstm", 64), ("tree_fc", 16))


class SmokeFailure(Exception):
    pass


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_err(a, b) -> float:
    """max |a - b| over max |b| (a tree of arrays or one array)."""
    import jax
    la = [np.asarray(x, np.float64) for x in jax.tree.leaves(a)]
    lb = [np.asarray(x, np.float64) for x in jax.tree.leaves(b)]
    num = max(float(np.max(np.abs(x - y))) for x, y in zip(la, lb))
    den = max(float(np.max(np.abs(y))) for y in lb)
    return num / max(den, 1e-30)


def report(phase: str, **fields) -> None:
    parts = [f"phase={phase}"]
    for k, v in fields.items():
        if isinstance(v, float):
            v = repr(v)
        elif isinstance(v, (list, tuple)):
            v = "[" + ",".join(repr(x if isinstance(x, int) else float(x))
                               for x in v) + "]"
        parts.append(f"{k}={v}")
    print(" ".join(parts), flush=True)


# ---------------------------------------------------------------------------
# Shared model pieces
# ---------------------------------------------------------------------------

def corpus(model: str, n: int, seed: int):
    """``n`` graphs of ``model``'s paper structure, inputs and targets."""
    from repro.configs.paper import get_paper_model
    cfg = get_paper_model(model)
    rng = np.random.default_rng(seed)
    graphs = cfg.make_graphs(n, rng=rng)
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) * 0.5 for g in graphs]
    targets = rng.standard_normal((n, HIDDEN)).astype(np.float32) * 0.5
    fn = cfg.make_vertex(hidden=HIDDEN, input_dim=INPUT_DIM)
    return fn, graphs, inputs, targets


def per_sample_loss(fn, params, batch, fusion_mode: str):
    """Squared error of each root's hidden state against its target."""
    import jax.numpy as jnp
    from repro.core.scheduler import execute, readout_roots
    buf = execute(fn, params, batch["dev"], batch["ext"],
                  fusion_mode=fusion_mode).buf
    root_h = readout_roots(buf, batch["dev"])[:, -HIDDEN:]
    return jnp.mean((root_h - batch["target"]) ** 2, axis=-1)


def mean_loss(fn, fusion_mode: str):
    import jax.numpy as jnp

    def loss(params, batch):
        return jnp.mean(per_sample_loss(fn, params, batch, fusion_mode))
    return loss


def fit(trainer, state, graphs, inputs, targets, composer, pipeline):
    """Run ``STEPS`` steps on one repeated corpus; return the state,
    per-step losses and per-step wall seconds."""
    from repro.train import MetricLogger

    def epochs():
        while True:
            yield graphs, inputs, {"target": list(targets)}

    logger = MetricLogger(log_fn=lambda line: None)
    state, logger = trainer.fit(state, epochs(), steps=STEPS,
                                compose=composer, pipeline=pipeline,
                                logger=logger)
    rows = list(logger.history)
    losses = [r["loss"] for r in rows]
    times = logger.train_times
    return state, losses, times


def train_config(**kw):
    from repro.train import TrainConfig
    return TrainConfig(lr=1e-3, warmup_steps=1, total_steps=STEPS,
                       weight_decay=0.0, log_every=1, **kw)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_train(model: str, batch: int, seed: int) -> None:
    import jax
    from repro.pipeline import SchedulePipeline
    from repro.train import Trainer

    fn, graphs, inputs, targets = corpus(model, batch, seed)
    pipe = SchedulePipeline(ext_dim=INPUT_DIM)
    key = jax.random.PRNGKey(seed)

    # Reference check at the initial parameters, on the batch as packed.
    pb = pipe.pack(graphs, inputs)
    b0 = {"dev": pb.dev, "ext": pb.ext, "target": jax.numpy.asarray(targets)}
    params0 = fn.init(key)
    t0 = time.perf_counter()
    fused = jax.jit(jax.value_and_grad(mean_loss(fn, "megastep")))
    loss_f, grad_f = fused(params0, b0)
    jax.block_until_ready(grad_f)
    t_ref0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        loss_r, grad_r = jax.jit(jax.value_and_grad(mean_loss(fn, "none")))(
            params0, b0)
        jax.block_until_ready(grad_r)
    t_ref = time.perf_counter() - t_ref0
    loss_err = abs(float(loss_f) - float(loss_r)) / abs(float(loss_r))
    grad_err = rel_err(grad_f, grad_r)

    trainer = Trainer(lambda p, b: (mean_loss(fn, "megastep")(p, b), {}),
                      fn.init, train_config())
    state = trainer.init_state(key)
    state, losses, times = fit(trainer, state, graphs, inputs, targets,
                               pipe.composer(batch), pipe)
    step1_err = abs(losses[0] - float(loss_r)) / abs(float(loss_r))
    sched = pb.sched
    report("train", model=model, batch=batch, T=sched.T, M=sched.M,
           A=sched.A, compile_s=times[0] - float(np.mean(times[1:])),
           step_s=float(np.mean(times[1:])),
           grad_check_s=t_ref0 - t0, ref_s=t_ref, loss=losses,
           loss_ref=float(loss_r), loss_err=loss_err, grad_err=grad_err,
           trainer_step1_err=step1_err, tol=TOL)
    _check(all(np.isfinite(losses)), f"{model}: non-finite loss {losses}")
    _check(losses[-1] < losses[0],
           f"{model}: loss did not fall over {STEPS} steps: {losses}")
    _check(loss_err <= TOL, f"{model}: loss err {loss_err} > {TOL}")
    _check(grad_err <= TOL, f"{model}: grad err {grad_err} > {TOL}")
    _check(step1_err <= TOL,
           f"{model}: trainer step-1 loss err {step1_err} > {TOL}")


def phase_serve(seed: int, n_requests: int = 32) -> None:
    import jax
    from repro.core.scheduler import execute, readout_roots
    from repro.core.structure import random_binary_tree
    from repro.models.treelstm import TreeLSTMVertex
    from repro.pipeline import BucketPolicy, SchedulePipeline
    from repro.serve import ContinuousBatchEngine, ContinuousRequest

    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=HIDDEN, arity=2)
    params = fn.init(jax.random.PRNGKey(seed))
    eng = ContinuousBatchEngine(fn, params, num_rows=4096,
                                frontier_width=256, fusion_mode="megastep")
    rng = np.random.default_rng(seed)
    ref_pipe = SchedulePipeline(ext_dim=INPUT_DIM,
                                bucket_policy=BucketPolicy(mode="pow2"),
                                with_runs=False)
    score = jax.jit(lambda p, dev, ext: readout_roots(
        execute(fn, p, dev, ext, fusion_mode="none").buf, dev))

    walls, errs = [], []
    for rnd in range(2):
        reqs = []
        for i in range(n_requests):
            g = random_binary_tree(int(rng.integers(2, 55)), rng)
            x = rng.standard_normal((g.num_nodes, INPUT_DIM)) \
                .astype(np.float32) * 0.5
            reqs.append(ContinuousRequest(request_id=rnd * n_requests + i,
                                          graph=g, inputs=x))
        t0 = time.perf_counter()
        for r in reqs:
            _check(eng.submit(r), f"request {r.request_id} rejected: "
                                  f"{r.error}")
        eng.run()
        walls.append(time.perf_counter() - t0)
        h = eng.health()
        bad = [(r.request_id, r.status, r.error) for r in reqs
               if r.status != "ok"]
        _check(not bad, f"serve: requests not ok: {bad[:4]} "
                        f"(last degradation: {h['last_degradation']})")
        _check(h["degradations"] == 0,
               f"serve: {h['degradations']} degradation(s), last: "
               f"{h['last_degradation']}")
        _check(not h["breaker_open"], "serve: circuit breaker open")
        with jax.default_matmul_precision("highest"):
            for r in reqs:
                pb = ref_pipe.pack([r.graph], [r.inputs])
                want = np.asarray(score(params, pb.dev, pb.ext))[0]
                errs.append(rel_err(r.root_state, want))
    err = max(errs)
    report("serve", model="tree_lstm", requests=2 * n_requests,
           compile_s=walls[0] - walls[1], run_s=walls[1],
           ticks=eng.ticks, windows=eng.windows,
           degradations=eng.health()["degradations"], root_err=err, tol=TOL)
    _check(err <= TOL, f"serve: root state err {err} > {TOL}")


def phase_sharded(seed: int, chips: int, batch: int = 64) -> None:
    import jax
    import jax.numpy as jnp
    from repro.dist.elastic import remesh
    from repro.pipeline import SchedulePipeline, ShardedPipeline
    from repro.train import Trainer

    fn, graphs, inputs, targets = corpus("tree_lstm", batch, seed)
    key = jax.random.PRNGKey(seed)

    def weighted_sum(p, b):
        per = per_sample_loss(fn, p, b, "megastep")
        return jnp.sum(per * b["weights"]), {}

    mesh = remesh(jax.devices(), {"data": chips})
    spipe = ShardedPipeline(ext_dim=INPUT_DIM, num_shards=chips)
    tr = Trainer(weighted_sum, fn.init,
                 train_config(dp_shard=True, compress_grads=True), mesh=mesh)
    state = tr.init_state(key)
    state, loss_sh, t_sh = fit(tr, state, graphs, inputs, targets,
                               spipe.composer(batch), spipe)
    # Each replica must have run on its own chip: the per-replica EF
    # residual is sharded over the data axis, one shard per device, and
    # every shard was written by its replica's quantization.
    leaf = jax.tree.leaves(state.ef)[0]
    shard_devs = sorted(s.device.id for s in leaf.addressable_shards)
    live = [float(jnp.sum(jnp.abs(s.data))) > 0
            for s in leaf.addressable_shards]

    pipe = SchedulePipeline(ext_dim=INPUT_DIM)
    base = Trainer(lambda p, b: (mean_loss(fn, "megastep")(p, b), {}),
                   fn.init, train_config())
    bstate = base.init_state(key)
    bstate, loss_1, t_1 = fit(base, bstate, graphs, inputs, targets,
                              pipe.composer(batch), pipe)
    errs = [abs(a - b) / abs(b) for a, b in zip(loss_sh, loss_1)]
    report("sharded", model="tree_lstm", batch=batch, replicas=chips,
           compile_s=t_sh[0] - float(np.mean(t_sh[1:])),
           step_s=float(np.mean(t_sh[1:])),
           baseline_step_s=float(np.mean(t_1[1:])), loss=loss_sh,
           loss_baseline=loss_1, loss_err=max(errs), tol=TOL_SHARDED,
           shard_devices=shard_devs)
    _check(all(np.isfinite(loss_sh)), f"sharded: non-finite loss {loss_sh}")
    _check(len(set(shard_devs)) == chips,
           f"sharded: replicas on devices {shard_devs}, want {chips} chips")
    _check(all(live), f"sharded: EF residual empty on some replica: {live}")
    _check(max(errs) <= TOL_SHARDED,
           f"sharded: loss err {max(errs)} > {TOL_SHARDED}")


def dispatch_counters() -> dict:
    from repro.obs.registry import get_registry
    snap = get_registry().snapshot()["counters"]
    return {k: v for k, v in sorted(snap.items())
            if k.startswith("kernel.dispatch")}


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only sharded training vs its baseline")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    impl = os.environ.get("REPRO_KERNEL_IMPL")
    if impl not in (None, "", "pallas"):
        print(f"chip_smoke: REPRO_KERNEL_IMPL={impl!r} pins a non-Pallas "
              f"backend; unset it or set it to 'pallas'", file=sys.stderr)
        return 2
    try:
        import jax
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    print(f"device: {devs[0].device_kind} x{len(devs)}  "
          f"compile cache: {cache_dir}", flush=True)

    t_start = time.perf_counter()
    try:
        if args.chips == 4:
            phase_sharded(args.seed, args.chips)
        else:
            for model, batch in TRAIN_CELLS:
                phase_train(model, batch, args.seed)
            phase_serve(args.seed)
        print(f"total_s={time.perf_counter() - t_start!r}")
        counters = dispatch_counters()
        for k, v in counters.items():
            print(f"{k} {v}")
        off = [k for k in counters
               if "megastep" in k and "impl=pallas" not in k]
        _check(not off, f"megastep ops not served by pallas: {off}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
