"""The distributed trainer: pjit'd train step, accumulation, checkpoints,
failure recovery.

One class owns the full loop a 1000-node job runs:

  - builds the jitted ``train_step`` with explicit in/out shardings
    (params per ``dist.sharding.param_specs``, batch over the DP axes,
    optimizer state congruent with params);
  - microbatch gradient accumulation (``optim.accum``) with the data
    collective amortized across microbatches;
  - optional int8+error-feedback gradient compression on the cross-pod
    reduction (``dist.compress``) — the slow-link optimization, with
    the EF residual carried in ``TrainState`` (skip-step-safe, never
    checkpointed);
  - data-parallel sharding over graphs (``dp_shard``): one packed
    ``LevelSchedule`` per replica, megastep under ``shard_map`` on the
    mesh's data axis, batches stacked ``[R, ...]`` by
    ``pipeline.ShardedPipeline`` from the composer's node-balanced
    :class:`~repro.pipeline.composer.ShardedStep`s;
  - async keep-k checkpoints (``checkpoint.manager``) and auto-resume
    (crash → restart → ``maybe_restore`` → identical trajectory,
    verified by tests);
  - failure injection hooks (``dist.fault``) so the recovery path is
    exercised in CI, not just documented.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import types
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.dist import sharding as shd
from repro.models.layers import axis_rules
from repro.obs import trace
from repro.optim import (OptState, adamw_init, adamw_update, microbatch_grads,
                         warmup_cosine)
from repro.train.metrics import MetricLogger

Params = Any
Batch = Dict[str, jax.Array]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TrainState:
    params: Params
    opt: OptState
    #: error-feedback residual for int8 gradient compression — a pytree
    #: congruent with ``params`` (``compress_grads`` without dp_shard)
    #: or with a leading ``[R]`` replica axis (dp_shard), ``None`` when
    #: compression is off.  Carried in the train state so the EF
    #: guarantee survives jit boundaries; NEVER checkpointed (stripped
    #: on save, zero-re-initialized after restore) so elastic restarts
    #: onto a different replica count stay shape-safe.
    ef: Optional[Any] = None

    @property
    def step(self) -> jax.Array:
        return self.opt.step


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    max_grad_norm: float = 1.0
    b1: float = 0.9
    b2: float = 0.95
    n_micro: int = 1                  # gradient-accumulation microbatches
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    ckpt_keep: int = 3
    log_every: int = 10
    compress_grads: bool = False      # int8+EF on the DP reduction
    #: data-parallel sharded training over graphs: batches are stacked
    #: ``[R, ...]`` pytrees (``ShardedPipeline.pack_step``) and the
    #: megastep runs under ``shard_map`` on the mesh's data axis, one
    #: ``LevelSchedule`` per replica.  ``loss_fn`` must then return a
    #: WEIGHTED SUM of per-sample losses (the batch carries a
    #: ``weights`` rider: 1.0 real, 0.0 filler) — the trainer reduces
    #: ``psum(sum)/psum(weight)`` so filler samples and ragged replicas
    #: cannot skew the global mean.  Requires ``mesh`` and
    #: ``n_micro == 1``.
    dp_shard: bool = False
    #: non-finite-gradient guard: a step whose loss or global grad norm
    #: is NaN/Inf is SKIPPED inside the jitted step (params and moments
    #: kept, step counter advanced — the poisoned batch is dropped) …
    skip_nonfinite: bool = True
    #: … up to this many CONSECUTIVE skips; one more aborts the run
    #: (persistent divergence is a bug, not weather).
    max_skip_steps: int = 10


class Trainer:
    def __init__(self, loss_fn: Callable[[Params, Batch], Tuple[jax.Array, Dict]],
                 init_params_fn: Callable[[jax.Array], Params],
                 cfg: TrainConfig, *,
                 mesh: Optional[Mesh] = None,
                 policy: Optional[shd.ShardingPolicy] = None):
        self.loss_fn = loss_fn
        self.init_params_fn = init_params_fn
        self.cfg = cfg
        self.mesh = mesh
        self.policy = policy or (shd.policy_for_mesh(mesh) if mesh else None)
        self.schedule = warmup_cosine(cfg.lr, cfg.warmup_steps,
                                      cfg.total_steps)
        self.ckpt = (CheckpointManager(cfg.ckpt_dir, keep=cfg.ckpt_keep,
                                       save_interval_steps=cfg.ckpt_every)
                     if cfg.ckpt_dir else None)
        self._train_step = None
        self._init_rng = None            # recorded by init_state for
        #                                  crash-before-first-commit re-init
        if cfg.dp_shard:
            if mesh is None:
                raise ValueError("dp_shard=True requires a mesh")
            if cfg.n_micro != 1:
                raise ValueError(
                    "dp_shard composes per-replica sub-batches instead "
                    "of microbatching — set n_micro=1")

    # ------------------------------------------------------------------
    # State init / restore
    # ------------------------------------------------------------------
    def _dp_axis(self) -> str:
        return next(a for a in self.mesh.axis_names if a != "model")

    def _fresh_ef(self, params: Params) -> Optional[Any]:
        """Zeroed error-feedback residual matching the current config:
        per-replica ``[R, ...]`` under dp_shard, param-shaped
        otherwise, ``None`` when compression is off."""
        if not self.cfg.compress_grads:
            return None
        if self.cfg.dp_shard:
            n = int(self.mesh.shape[self._dp_axis()])
            return jax.tree.map(
                lambda p: jnp.zeros((n,) + p.shape, p.dtype), params)
        return jax.tree.map(jnp.zeros_like, params)

    def init_state(self, rng: jax.Array) -> TrainState:
        self._init_rng = rng
        if self.mesh is not None:
            specs = None

            def make():
                p = self.init_params_fn(rng)
                return TrainState(params=p, opt=adamw_init(p),
                                  ef=self._fresh_ef(p))

            abstract = jax.eval_shape(make)
            specs = self._state_specs(abstract)
            with self.mesh:
                state = jax.jit(make, out_shardings=shd.shardings_for(
                    abstract, specs, self.mesh))()
            return state
        p = self.init_params_fn(rng)
        return TrainState(params=p, opt=adamw_init(p),
                          ef=self._fresh_ef(p))

    def _state_specs(self, abstract_state) -> Any:
        pspecs = shd.param_specs(abstract_state.params, self.mesh,
                                 self.policy)
        ef_specs = None
        if getattr(abstract_state, "ef", None) is not None:
            if self.cfg.dp_shard:
                # per-replica residual: shard the leading [R] axis
                ax = self._dp_axis()
                ef_specs = jax.tree.map(lambda _: P(ax),
                                        abstract_state.ef)
            else:
                ef_specs = pspecs
        return TrainState(
            params=pspecs,
            opt=OptState(step=P(), mu=pspecs, nu=pspecs),
            ef=ef_specs)

    def maybe_restore(self, state: TrainState) -> Tuple[TrainState, int]:
        """Resume from the newest committed checkpoint, resharding onto
        the current mesh (elastic restart).

        Checkpoints never carry the EF residual (its shape depends on
        the replica count, which an elastic restart changes), so the
        residual is stripped before matching the manifest and
        re-initialized to zeros for the new mesh — EF restarts cold,
        which only forfeits at most one step's quantization error."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return state, 0
        bare = dataclasses.replace(state, ef=None)
        sharding_fn = None
        if self.mesh is not None:
            specs = self._state_specs(jax.eval_shape(lambda: bare))
            flat_specs = dict(_flatten(specs))

            def sharding_fn(key, leaf, _m=self.mesh, _f=flat_specs):
                spec = _f.get(key, P())
                return NamedSharding(_m, spec)

        restored, step = self.ckpt.restore(bare, sharding_fn=sharding_fn)
        if self.cfg.compress_grads:
            restored = dataclasses.replace(
                restored, ef=self._fresh_ef(restored.params))
        return restored, step

    # ------------------------------------------------------------------
    # The jitted step
    # ------------------------------------------------------------------
    def _build_step(self, example_batch: Batch):
        cfg = self.cfg
        if cfg.dp_shard:
            return self._build_sharded_step(example_batch)

        grad_specs = None
        if self.mesh is not None:
            abstract_p = jax.eval_shape(
                lambda: self.init_params_fn(jax.random.PRNGKey(0)))
            grad_specs = shd.param_specs(abstract_p, self.mesh, self.policy)

        def step_fn(state: TrainState, batch: Batch
                    ) -> Tuple[TrainState, Dict[str, jax.Array]]:
            rules = self.policy.rules(self.mesh) if self.policy else None

            def run():
                loss, grads, metrics = microbatch_grads(
                    self.loss_fn, state.params, batch, cfg.n_micro,
                    grad_specs=grad_specs)
                if cfg.compress_grads:
                    # Error-feedback quantization, residual in the
                    # train state: emit Q(g + e), carry e' = g + e -
                    # Q(g + e) — the module docstring's EF guarantee,
                    # previously advertised but not wired (grads were
                    # quantized with no residual, so per-step bias
                    # accumulated unchecked).
                    from repro.dist.compress import ef_apply
                    grads, new_ef = ef_apply(grads, state.ef)
                else:
                    new_ef = state.ef
                lr = self.schedule(state.opt.step)
                new_params, new_opt, opt_metrics = adamw_update(
                    state.params, grads, state.opt, lr=lr, b1=cfg.b1,
                    b2=cfg.b2, weight_decay=cfg.weight_decay,
                    max_grad_norm=cfg.max_grad_norm)
                metrics = dict(metrics)
                metrics.update(opt_metrics)
                metrics["loss"] = loss
                new_state = TrainState(params=new_params, opt=new_opt,
                                       ef=new_ef)
                if cfg.skip_nonfinite:
                    # Non-finite guard, resolved inside the jitted step
                    # (no host round-trip): a NaN/Inf loss or gradient
                    # keeps the old params and moments — the poisoned
                    # batch is dropped — but the step counter advances,
                    # so the lr schedule and checkpoint cadence move on.
                    # The EF residual is likewise kept: a skipped step
                    # emitted nothing, so folding the poisoned
                    # accumulator into the residual would leak the
                    # dropped batch into the next emission.
                    ok = (jnp.isfinite(loss)
                          & jnp.isfinite(opt_metrics["grad_norm"]))
                    kept = TrainState(
                        params=state.params,
                        opt=OptState(step=new_opt.step, mu=state.opt.mu,
                                     nu=state.opt.nu),
                        ef=state.ef)
                    new_state = jax.tree.map(
                        lambda a, b: jnp.where(ok, a, b), new_state, kept)
                    metrics["skipped"] = 1.0 - ok.astype(jnp.float32)
                return new_state, metrics

            if rules is not None:
                with axis_rules(rules):
                    return run()
            return run()

        if self.mesh is None:
            return jax.jit(step_fn, donate_argnums=(0,))

        abstract_state = jax.eval_shape(
            lambda: TrainState(params=self.init_params_fn(
                jax.random.PRNGKey(0)), opt=adamw_init(
                    self.init_params_fn(jax.random.PRNGKey(0)))))
        state_specs = self._state_specs(abstract_state)
        batch_specs = shd.batch_specs(
            self.policy, self.mesh,
            {k: v.shape for k, v in example_batch.items()})
        state_sh = shd.shardings_for(abstract_state, state_specs, self.mesh)
        batch_sh = {k: NamedSharding(self.mesh, s)
                    for k, s in batch_specs.items()}
        return jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, None),
                       donate_argnums=(0,))

    def _build_sharded_step(self, example_batch: Batch):
        """The dp_shard train step: one ``LevelSchedule`` per replica,
        megastep under ``shard_map`` on the mesh's data axis.

        Batch leaves carry a leading ``[R]`` axis
        (``ShardedPipeline.pack_step``); each replica squeezes its
        slice and runs ``loss_fn`` on its own schedule.  ``loss_fn``
        returns a WEIGHTED SUM of per-sample losses, so the global
        objective is ``psum(sum) / psum(weights)`` and the global
        gradient is ``psum(g_local) / total`` — exactly the mean the
        single-replica union batch would produce, to fp roundoff.
        With ``compress_grads`` the reduction routes through
        ``dist.compress.cross_pod_mean_int8_ef_tree``: each replica
        quantizes ``g_local * R / total`` plus its residual to int8,
        the psum averages the emitted payloads, and the new residual
        lands back in ``TrainState.ef`` (leading ``[R]`` axis, sharded
        with the batch)."""
        import functools as _ft

        from repro.dist.compress import cross_pod_mean_int8_ef_tree

        cfg = self.cfg
        mesh = self.mesh
        axis = self._dp_axis()
        n_rep = int(mesh.shape[axis])

        def replica_step(params, ef, batch):
            # Everything here is per-replica: leaves arrive with a
            # leading [1] shard axis; outputs return one too (out_specs
            # P(axis) reassembles them — check_vma stays off because a
            # DeviceSchedule pytree is opaque to the varying-axes
            # checker).
            local = jax.tree.map(lambda a: a[0], batch)

            def objective(p):
                return self.loss_fn(p, local)

            (loss_sum, metrics), g = jax.value_and_grad(
                objective, has_aux=True)(params)
            count = jnp.sum(local["weights"]).astype(jnp.float32)
            total = jax.lax.psum(count, axis)
            loss_total = jax.lax.psum(loss_sum.astype(jnp.float32), axis)
            if cfg.compress_grads:
                scale = n_rep / total
                ef_local = jax.tree.map(lambda a: a[0], ef)
                g_mean, new_ef = cross_pod_mean_int8_ef_tree(
                    jax.tree.map(lambda x: x * scale, g), ef_local,
                    axis_name=axis)
                new_ef = jax.tree.map(lambda x: x[None], new_ef)
            else:
                g_mean = jax.tree.map(
                    lambda x: jax.lax.psum(x, axis) / total, g)
                new_ef = ef
            metrics = jax.tree.map(
                lambda m: jax.lax.pmean(m, axis)[None], metrics)
            return (loss_total[None], total[None],
                    jax.tree.map(lambda x: x[None], g_mean),
                    new_ef, metrics)

        sharded = _ft.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
            check_vma=False)(replica_step)

        def step_fn(state: TrainState, batch: Batch
                    ) -> Tuple[TrainState, Dict[str, jax.Array]]:
            loss_t, total_t, g_s, new_ef, metrics_s = sharded(
                state.params, state.ef, batch)
            loss_total, total = loss_t[0], total_t[0]
            grads = jax.tree.map(lambda x: x[0], g_s)  # psum'd: all equal
            metrics = jax.tree.map(lambda m: m[0], metrics_s)
            lr = self.schedule(state.opt.step)
            new_params, new_opt, opt_metrics = adamw_update(
                state.params, grads, state.opt, lr=lr, b1=cfg.b1,
                b2=cfg.b2, weight_decay=cfg.weight_decay,
                max_grad_norm=cfg.max_grad_norm)
            metrics = dict(metrics)
            metrics.update(opt_metrics)
            loss = loss_total / total
            metrics["loss"] = loss
            new_state = TrainState(params=new_params, opt=new_opt,
                                   ef=new_ef)
            if cfg.skip_nonfinite:
                # Same guard as the unsharded leg — and the same EF
                # rule: a skipped step emitted nothing, so every
                # replica's residual stays bit-identical.
                ok = (jnp.isfinite(loss)
                      & jnp.isfinite(opt_metrics["grad_norm"]))
                kept = TrainState(
                    params=state.params,
                    opt=OptState(step=new_opt.step, mu=state.opt.mu,
                                 nu=state.opt.nu),
                    ef=state.ef)
                new_state = jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b), new_state, kept)
                metrics["skipped"] = 1.0 - ok.astype(jnp.float32)
            return new_state, metrics

        return jax.jit(step_fn, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # The loop
    # ------------------------------------------------------------------
    def fit(self, state: TrainState, batches, *,
            steps: Optional[int] = None,
            logger: Optional[MetricLogger] = None,
            fault_injector=None,
            compose=None,
            pipeline=None) -> Tuple[TrainState, MetricLogger]:
        """Run ``steps`` optimizer steps (or cfg.total_steps).

        ``fault_injector`` (``dist.fault.FaultInjector``) may raise a
        simulated node failure; the loop recovers by restoring the last
        committed checkpoint — the 1000-node restart policy in

        miniature.

        ``batches`` may come straight from the schedule pipeline
        (``repro.pipeline``): batch values may be arbitrary pytrees
        (e.g. a ``DeviceSchedule``), and a loader exposing ``close()``
        (``PrefetchLoader`` / ``AsyncPacker``) has its background
        producer shut down when the loop exits.

        ``compose=`` (opt-in) enables pipeline-aware batch formation: a
        ``repro.pipeline.BatchComposer`` plus a ``pipeline=``
        ``SchedulePipeline``.  ``batches`` must then yield EPOCH corpora
        — ``(graphs, inputs)`` or ``(graphs, inputs, aux)`` tuples —
        and each epoch is re-composed into cache-friendly minibatches
        before packing.  NOTE composition REORDERS samples within an
        epoch (losslessly: every sample exactly once); aux riders (e.g.
        labels) are permuted in lockstep with their samples, and every
        batch dict carries ``sample_ids`` (original corpus indices) so
        per-sample outputs can be realigned.  Batch dicts are
        ``{"dev": DeviceSchedule, "ext": array, **aux, "sample_ids"}``.
        """
        cfg = self.cfg
        steps = steps if steps is not None else cfg.total_steps
        logger = logger or MetricLogger()
        source = batches        # the caller's object owns any close()
        if compose is not None and compose is not False:
            # (False is accepted as the natural opt-out spelling)
            if not callable(getattr(compose, "compose", None)):
                raise ValueError(
                    f"compose= takes a repro.pipeline.BatchComposer "
                    f"(or False to opt out), got {compose!r}")
            if pipeline is None:
                raise ValueError("compose= requires pipeline= "
                                 "(a SchedulePipeline to pack through)")
            if cfg.dp_shard:
                if not hasattr(pipeline, "pack_step"):
                    raise ValueError(
                        "dp_shard=True composition requires pipeline= "
                        "a repro.pipeline.ShardedPipeline (one "
                        "schedule cache per replica)")
                n_rep = int(self.mesh.shape[self._dp_axis()])
                if pipeline.num_shards != n_rep:
                    raise ValueError(
                        f"pipeline has {pipeline.num_shards} shards "
                        f"but the mesh data axis has {n_rep} devices")
                batches = _sharded_stream(batches, compose, pipeline)
            else:
                batches = _composed_stream(batches, compose, pipeline)
        try:
            return self._fit(state, batches, steps, logger, fault_injector)
        finally:
            # Shut down background producers (PrefetchLoader/AsyncPacker)
            # — but not plain generators, which every generator-`close()`
            # would kill even though the caller may keep consuming it
            # across fit() calls.
            close = getattr(source, "close", None)
            if callable(close) and not isinstance(source,
                                                  types.GeneratorType):
                close()

    def _fit(self, state: TrainState, batches: Iterator[Batch], steps: int,
             logger: MetricLogger, fault_injector) -> Tuple[TrainState,
                                                            MetricLogger]:
        cfg = self.cfg
        start = int(np.asarray(state.step))
        if cfg.compress_grads and state.ef is None:
            # States built before compression was enabled (or restored
            # from an EF-free checkpoint) start with a cold residual.
            state = dataclasses.replace(
                state, ef=self._fresh_ef(state.params))

        ctx = self.mesh if self.mesh is not None else _nullctx()
        with ctx:
            if self._train_step is None:
                first = next(batches)
                with trace.span("train.build_step"):
                    self._train_step = self._build_step(first)
                batches = _chain_first(first, batches)

            done = start
            skips_in_row = 0
            while done < steps:
                with trace.correlate(step=done), \
                        trace.span("train.step", step=done):
                    state, done, skips_in_row = self._fit_one(
                        state, batches, done, steps, skips_in_row,
                        logger, fault_injector)
            if self.ckpt is not None:
                self.ckpt.save(_ckpt_view(state), done, blocking=True)
        return state, logger

    def _fit_one(self, state, batches, done, steps, skips_in_row,
                 logger, fault_injector):
        """One iteration of the fit loop (factored out so the whole
        body sits under one ``train.step`` span with ``step=done``
        correlation).  Returns ``(state, done, skips_in_row)``; a fault
        recovery leaves ``done`` rewound instead of advanced."""
        cfg = self.cfg
        batch = next(batches)
        with trace.span("train.h2d"):
            batch = {k: jax.tree.map(jnp.asarray, v)
                     for k, v in batch.items()}
        t0 = time.perf_counter()
        try:
            if fault_injector is not None:
                fault_injector.tick(done)
            with trace.span("train.fwd_bwd"):
                state, metrics = self._train_step(state, batch)
                trace.maybe_block(metrics)
        except _FAULTS as e:
            if self.ckpt is None:
                raise
            # Node failure: restore last commit and continue.
            trace.instant("train.fault", step=done, error=repr(e))
            with trace.span("train.restore"):
                self.ckpt.wait()
                if self.ckpt.latest_step() is None:
                    # Crashed before the FIRST commit: there is
                    # nothing to restore, so re-init from the
                    # recorded init rng — restoring into the zeroed
                    # twin here used to resume from all-zero params
                    # (a silently different model).
                    rng = (self._init_rng if self._init_rng is not None
                           else jax.random.PRNGKey(0))
                    return self.init_state(rng), 0, skips_in_row
                # state was donated — rebuild an abstract twin to
                # restore into.
                abstract = jax.eval_shape(
                    lambda: TrainState(
                        params=self.init_params_fn(jax.random.PRNGKey(0)),
                        opt=adamw_init(self.init_params_fn(
                            jax.random.PRNGKey(0)))))
                zeros = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), abstract)
                state, done = self.maybe_restore(zeros)
            return state, done, skips_in_row
        if cfg.skip_nonfinite:
            # NOTE this read syncs on the step's metrics, so the
            # train_tick below measures executed train work (not just
            # dispatch) whenever the guard is on — and always does
            # under tracing, via the maybe_block above.
            if float(np.asarray(metrics.get("skipped", 0.0))) > 0:
                skips_in_row += 1
                logger.count("nonfinite_skips")
                if skips_in_row > cfg.max_skip_steps:
                    raise RuntimeError(
                        f"aborting at step {done}: "
                        f"{skips_in_row} consecutive non-finite "
                        f"steps (max_skip_steps="
                        f"{cfg.max_skip_steps}) — the model has "
                        f"diverged, skipping batches cannot "
                        f"save it")
            else:
                skips_in_row = 0
        logger.train_tick(time.perf_counter() - t0)
        done += 1
        if done % cfg.log_every == 0 or done == steps:
            with trace.span("train.log"):
                logger.log(done, metrics)
        if self.ckpt is not None and self.ckpt.should_save(done):
            with trace.span("train.checkpoint", step=done):
                self.ckpt.save(_ckpt_view(state), done)
        return state, done, skips_in_row


class _nullctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def _ckpt_view(state: TrainState) -> TrainState:
    """What checkpoints carry: the state WITHOUT the EF residual.  The
    residual's shape depends on the replica count, so persisting it
    would pin checkpoints to one mesh size and break elastic restarts;
    restore re-initializes it to zeros instead (see
    :meth:`Trainer.maybe_restore`)."""
    return dataclasses.replace(state, ef=None)


def _composed_stream(epochs, composer, pipeline):
    """Turn a stream of epoch corpora into composed, packed batch dicts
    (the ``compose=`` leg of :meth:`Trainer.fit`).

    Each epoch tuple is ``(graphs, inputs)`` or ``(graphs, inputs,
    aux)``; the composer reorders it into same-fingerprint groups +
    greedy leftover fills, the pipeline packs each composed batch
    (cache/bucket/persist-aware) on its ASYNC prefetch stage — host
    packing overlaps device compute, same as every other production
    path — and the batch dict carries the aux riders and
    ``sample_ids`` realigned to the composed order."""

    def items():
        for epoch in epochs:
            graphs, inputs = epoch[0], epoch[1]
            aux = epoch[2] if len(epoch) > 2 else None
            for name in ("dev", "ext"):
                if aux and name in aux:
                    raise ValueError(
                        f"aux rider name {name!r} is reserved — "
                        f"composed batch dicts carry the "
                        f"DeviceSchedule/external matrix under that key")
            batches, _ = composer.compose(graphs, inputs, aux)
            for cb in batches:
                yield cb.as_item()

    packer = pipeline.prefetch(items(), depth=2)
    try:
        for pb in packer:
            batch = {"dev": pb.dev, "ext": pb.ext}
            for name, vals in pb.aux.items():
                batch[name] = np.asarray(vals)
            yield batch
    finally:
        packer.close()                    # runs on close()/GC of this
        # generator after fit() abandons it — the background packer
        # never outlives the loop observably (daemon thread regardless)


def _sharded_stream(epochs, composer, pipeline):
    """The dp_shard twin of :func:`_composed_stream`: each epoch corpus
    is composed into node-balanced :class:`ShardedStep`s
    (``BatchComposer.compose_sharded``), every replica's sub-batch is
    packed through its own per-replica cache on the async prefetch
    stage, and the yielded batch dicts carry stacked ``[R, ...]``
    leaves plus the ``weights``/``sample_ids`` riders the sharded step
    reduces with."""

    def steps():
        for epoch in epochs:
            graphs, inputs = epoch[0], epoch[1]
            aux = epoch[2] if len(epoch) > 2 else None
            for name in ("dev", "ext"):
                if aux and name in aux:
                    raise ValueError(
                        f"aux rider name {name!r} is reserved — "
                        f"composed batch dicts carry the "
                        f"DeviceSchedule/external matrix under that key")
            sharded_steps, _ = composer.compose_sharded(
                graphs, inputs, aux, num_shards=pipeline.num_shards)
            for st in sharded_steps:
                yield st

    packer = pipeline.prefetch(steps(), depth=2)
    try:
        for batch in packer:
            yield batch
    finally:
        packer.close()


def _chain_first(first, rest):
    # Explicit next() rather than `yield from`: when this wrapper is
    # abandoned after the loop, its close() must NOT propagate into the
    # caller-owned `rest` iterator (yield-from delegates GeneratorExit,
    # which would close a generator the caller may reuse).
    yield first
    while True:
        try:
            item = next(rest)
        except StopIteration:
            return
        yield item


def _flatten(tree, prefix=()):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, P))
    for path, leaf in flat:
        key = "/".join(_pstr(p) for p in path)
        yield key, leaf


def _pstr(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return f"[{p.idx}]"
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


from repro.dist.fault import SimulatedFailure  # noqa: E402 (cycle-free)

_FAULTS = (SimulatedFailure,)
