"""CI chaos suite (PR 6 tentpole): drive :class:`ScriptedChaos` through
every instrumented site — cold packs, persist load/store, the prefetch
thread, kernel launches, NaN batches — and assert the robustness
invariants end to end:

  * every submitted request reaches EXACTLY ONE terminal status and
    lands in ``engine.finished`` (nothing lost, nothing duplicated);
  * a poisoned batch is quarantined by bisection: only the offending
    request fails, co-batched peers still complete;
  * a NaN injection fails ONLY the poisoned request — its peers'
    results are bit-identical to the fault-free run (same batch, same
    compiled program);
  * kernel failures degrade to the op-by-op oracle; after
    ``breaker_threshold`` consecutive failures the circuit breaker pins
    the oracle and the fused path is never re-tried;
  * persist/prefetch faults are absorbed (counted miss / transient
    retry) without changing any result;
  * training through a chaos-injected pipeline converges to the SAME
    final state as the fault-free run (transient faults are invisible
    to the learner).

Plus the hypothesis property test: under ANY interleaving of submits,
deadlines, queue pressure and injected faults, the multiset of terminal
requests equals the multiset submitted, and every completed request's
result matches the fault-free reference.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.structure import chain, random_binary_tree
from repro.dist.fault import (ScriptedChaos, SimulatedFailure, chaos_fire,
                              get_chaos, install_chaos)
from repro.models.rnn import LSTMVertex
from repro.models.treelstm import TreeLSTMVertex
from repro.pipeline import (BucketPolicy, ScheduleCache, SchedulePipeline,
                            batch_fingerprint)
from repro.pipeline.persist import SchedulePersist
from repro.serve import (CircuitBreaker, StructureRequest,
                         StructureServeEngine, TERMINAL, VertexRequest,
                         VertexServeEngine)
from repro.serve.robustness import FAILED, OK, REJECTED, RequestLifecycle
from tests.hypothesis_compat import given, settings, st

INPUT_DIM = 4


@pytest.fixture(scope="module")
def tree_fn():
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    return fn, fn.init(jax.random.PRNGKey(0))


def _structure_requests(seed, n, lo=2, hi=7):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        g = random_binary_tree(int(rng.integers(lo, hi)), rng)
        x = (rng.standard_normal((g.num_nodes, INPUT_DIM))
             .astype(np.float32) * 0.3)
        reqs.append(StructureRequest(request_id=i, graph=g, inputs=x))
    return reqs


def _clone(req, **over):
    return StructureRequest(request_id=req.request_id, graph=req.graph,
                            inputs=req.inputs, **over)


def _roots_by_id(engine):
    return {r.request_id: r.root_state for r in engine.finished
            if r.status == OK}


def _hermetic_engine(fn, params, **kw):
    """A StructureServeEngine whose schedule cache has NO disk tier:
    the cold-pack chaos site must fire even when the CI job points
    ``REPRO_SCHED_PERSIST`` at a shared store (a disk hit would skip
    the pack and defuse the injection)."""
    pipe = SchedulePipeline(fn.input_dim,
                            bucket_policy=BucketPolicy(mode="pow2"),
                            cache=ScheduleCache(capacity=128,
                                                persist=False),
                            with_runs=False)
    return StructureServeEngine(fn, params, pipeline=pipe, **kw)


# ---------------------------------------------------------------------------
# The hook itself
# ---------------------------------------------------------------------------

def test_scripted_chaos_fires_only_scripted_calls():
    hook = ScriptedChaos(fail={"pack": [1]})
    with install_chaos(hook):
        chaos_fire("pack")                      # call 0: clean
        with pytest.raises(SimulatedFailure):
            chaos_fire("pack")                  # call 1: injected
        chaos_fire("pack")                      # call 2: clean again
        chaos_fire("kernel")                    # unscripted site: clean
    assert hook.calls == {"pack": 3, "kernel": 1}
    assert hook.fired == {"pack": [1]}
    assert get_chaos() is None                  # uninstalled on exit
    chaos_fire("pack")                          # and the site is free


# ---------------------------------------------------------------------------
# Poison quarantine (StructureServeEngine + bisect)
# ---------------------------------------------------------------------------

def test_transient_batch_fault_recovers_every_request(tree_fn):
    """A fault that poisons the FULL batch but not its halves: the
    bisect retries both halves and every request still completes."""
    fn, params = tree_fn
    ref = _hermetic_engine(fn, params, batch_size=4, compose=False)
    for r in _structure_requests(7, 4):
        assert ref.submit(r)
    ref.run()
    want = _roots_by_id(ref)

    eng = _hermetic_engine(fn, params, batch_size=4, compose=False)
    for r in _structure_requests(7, 4):
        assert eng.submit(r)
    hook = ScriptedChaos(fail={"pack": [0]})    # only the 4-wide pack
    with install_chaos(hook):
        eng.run()

    assert hook.fired["pack"] == [0]
    assert all(r.status == OK for r in eng.finished)
    h = eng.health()
    assert h["quarantines"] == 1 and h["failed"] == 0
    assert h["completed"] == 4
    got = _roots_by_id(eng)
    assert sorted(got) == sorted(want)
    for rid in want:
        np.testing.assert_allclose(got[rid], want[rid],
                                   rtol=1e-5, atol=1e-6)


def test_persistent_poison_is_bisected_down_to_one_request(tree_fn):
    """Cold-pack call order under bisection over [A,B,C,D] is
    [ABCD], [AB], [A], [B], [CD] — failing calls {0, 1, 2} emulates a
    request (A) that poisons every batch containing it.  Only A reaches
    ``failed``; B, C, D complete with correct results."""
    fn, params = tree_fn
    ref = _hermetic_engine(fn, params, batch_size=4, compose=False)
    for r in _structure_requests(11, 4):
        assert ref.submit(r)
    ref.run()
    want = _roots_by_id(ref)

    eng = _hermetic_engine(fn, params, batch_size=4, compose=False)
    reqs = _structure_requests(11, 4)
    for r in reqs:
        assert eng.submit(r)
    hook = ScriptedChaos(fail={"pack": [0, 1, 2]})
    with install_chaos(hook):
        eng.run()

    assert hook.fired["pack"] == [0, 1, 2]
    assert reqs[0].status == FAILED
    assert "batch execution failed" in reqs[0].error
    assert reqs[0].root_state is None
    for peer in reqs[1:]:
        assert peer.status == OK, peer.error
        np.testing.assert_allclose(peer.root_state, want[peer.request_id],
                                   rtol=1e-5, atol=1e-6)
    h = eng.health()
    assert h["failed"] == 1 and h["completed"] == 3
    assert h["quarantines"] == 1
    assert len(eng.finished) == 4               # all terminal, none lost


def test_nan_injection_fails_only_poisoned_peer_bit_identical(tree_fn):
    """NaN-batch injection: the poisoned sample's whole external block
    is NaN, which is block-diagonal in the batched forward — only that
    request fails (``non-finite root state``), and because the batch
    composition and compiled program are UNCHANGED, the surviving
    peers' results are bit-identical to the fault-free run."""
    fn, params = tree_fn
    ref = StructureServeEngine(fn, params, batch_size=4, compose=False)
    for r in _structure_requests(3, 4):
        assert ref.submit(r)
    ref.run()
    want = _roots_by_id(ref)

    eng = StructureServeEngine(fn, params, batch_size=4, compose=False)
    reqs = _structure_requests(3, 4)
    for r in reqs:
        assert eng.submit(r)
    hook = ScriptedChaos(nan_ext={0: (1,)})     # poison sample 1 only
    with install_chaos(hook):
        eng.run()

    assert hook.fired["ext"] == [0]
    assert reqs[1].status == FAILED
    assert reqs[1].error == "non-finite root state"
    for k in (0, 2, 3):
        assert reqs[k].status == OK
        np.testing.assert_array_equal(reqs[k].root_state,
                                      want[reqs[k].request_id])
    h = eng.health()
    assert h["failed"] == 1 and h["completed"] == 3
    assert h["quarantines"] == 0                # attribution was direct


# ---------------------------------------------------------------------------
# Degradation ladder + circuit breaker
# ---------------------------------------------------------------------------

def test_kernel_chaos_degrades_then_breaker_pins_oracle(tree_fn):
    """Every kernel launch fails: the first ``breaker_threshold``
    batches each degrade to the oracle (correct results, counted), then
    the breaker opens and the fused path is NEVER re-tried — the
    ``kernel`` site stops firing entirely."""
    fn, params = tree_fn
    all_reqs = _structure_requests(5, 8)
    ref = StructureServeEngine(fn, params, batch_size=2, compose=False)
    for r in all_reqs:
        assert ref.submit(_clone(r))
    ref.run()
    want = _roots_by_id(ref)

    eng = StructureServeEngine(fn, params, batch_size=2, compose=False,
                               breaker_threshold=2)
    assert eng.fused
    for r in all_reqs:
        assert eng.submit(r)
    hook = ScriptedChaos(fail={"kernel": list(range(100))})
    with install_chaos(hook):
        eng.run()                               # 4 batches of 2

    assert hook.calls["kernel"] == 2            # pinned after 2 failures
    assert not eng.fused
    h = eng.health()
    assert h["degradations"] == 2
    assert h["last_degradation"].startswith("SimulatedFailure")
    assert h["breaker_open"] and h["breaker_trips"] == 1
    assert h["completed"] == 8 and h["failed"] == 0
    got = _roots_by_id(eng)
    for rid in want:
        np.testing.assert_allclose(got[rid], want[rid],
                                   rtol=1e-5, atol=1e-6)


def test_lifecycle_keeps_last_degradation_cause():
    """``health()`` names the exception behind the latest fused → oracle
    fallback, so an operator (or the chip smoke test) can say why the
    fused path was left."""
    lc = RequestLifecycle()
    assert lc.health()["last_degradation"] is None
    lc.degrade(ValueError("first"))
    lc.degrade(RuntimeError("Mosaic refused the kernel"))
    h = lc.health()
    assert h["degradations"] == 2
    assert h["last_degradation"] == "RuntimeError: Mosaic refused the kernel"


def test_vertex_engine_kernel_chaos_transient_recovery():
    """Sporadic kernel failures on the decode path: the faulted ticks
    run through the oracle, successes reset the breaker, and every
    request's final state still matches the fault-free engine."""
    fn = LSTMVertex(input_dim=INPUT_DIM, hidden=5)
    params = fn.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    inputs = [rng.standard_normal((L, INPUT_DIM)).astype(np.float32) * 0.3
              for L in (3, 5, 2, 4)]

    ref = VertexServeEngine(fn, params, num_slots=2, fusion_mode="megastep")
    for i, x in enumerate(inputs):
        assert ref.submit(VertexRequest(request_id=i, inputs=x))
    ref.run()
    want = {r.request_id: r.final_state for r in ref.finished}

    eng = VertexServeEngine(fn, params, num_slots=2, fusion_mode="megastep")
    for i, x in enumerate(inputs):
        assert eng.submit(VertexRequest(request_id=i, inputs=x))
    hook = ScriptedChaos(fail={"kernel": [0, 2]})
    with install_chaos(hook):
        eng.run()

    assert hook.fired["kernel"] == [0, 2]
    h = eng.health()
    assert h["degradations"] == 2
    assert not h["breaker_open"]                # successes reset it
    assert h["completed"] == 4 and h["failed"] == 0
    for r in eng.finished:
        assert r.status == OK
        np.testing.assert_allclose(r.final_state, want[r.request_id],
                                   rtol=1e-5, atol=1e-6)
    assert eng.fused                            # fused path still live


def test_vertex_engine_breaker_pins_after_streak():
    fn = LSTMVertex(input_dim=INPUT_DIM, hidden=5)
    params = fn.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    inputs = [rng.standard_normal((4, INPUT_DIM)).astype(np.float32) * 0.3
              for _ in range(3)]
    eng = VertexServeEngine(fn, params, num_slots=3,
                            fusion_mode="megastep", breaker_threshold=2)
    for i, x in enumerate(inputs):
        assert eng.submit(VertexRequest(request_id=i, inputs=x))
    hook = ScriptedChaos(fail={"kernel": list(range(100))})
    with install_chaos(hook):
        eng.run()
    assert hook.calls["kernel"] == 2            # never re-tried once open
    assert not eng.fused
    h = eng.health()
    assert h["breaker_open"] and h["degradations"] == 2
    assert h["completed"] == 3 and h["failed"] == 0


def test_vertex_engine_total_tick_failure_fails_inflight_only():
    """Both rungs of the ladder down: the tick's in-flight requests
    reach ``failed`` (buffer untouched), queued requests are admitted —
    and fail — on LATER ticks; nothing hangs, nothing is lost."""
    fn = LSTMVertex(input_dim=INPUT_DIM, hidden=5)
    params = fn.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(4)
    eng = VertexServeEngine(fn, params, num_slots=2,
                            fusion_mode="megastep", breaker_threshold=1)

    def oracle_down(*args):
        raise SimulatedFailure("oracle down")

    eng._tick_oracle = oracle_down
    reqs = [VertexRequest(request_id=i,
                          inputs=rng.standard_normal((3, INPUT_DIM))
                          .astype(np.float32))
            for i in range(3)]
    for r in reqs:
        assert eng.submit(r)
    hook = ScriptedChaos(fail={"kernel": list(range(100))})
    with install_chaos(hook):
        live = eng.step()                       # first 2 slots fail
    assert sorted(r.status for r in reqs) == [FAILED, FAILED, "pending"]
    assert live == 1                            # third still queued
    with install_chaos(ScriptedChaos(fail={"kernel": list(range(100))})):
        eng.run()
    assert all(r.status == FAILED for r in reqs)
    assert all("tick failed" in r.error for r in reqs)
    assert len(eng.finished) == 3


# ---------------------------------------------------------------------------
# Pipeline sites: prefetch retries, persist misses
# ---------------------------------------------------------------------------

def _batch_stream(seed, n_batches, bs=3):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        graphs = [random_binary_tree(int(rng.integers(2, 6)), rng)
                  for _ in range(bs)]
        inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
                  .astype(np.float32) * 0.3 for g in graphs]
        out.append((graphs, inputs))
    return out

def test_prefetch_chaos_is_retried_transparently():
    source = _batch_stream(0, 4)
    clean = SchedulePipeline(INPUT_DIM, bucket_policy=BucketPolicy())
    want = [np.asarray(clean.pack(g, x).ext) for g, x in source]

    pipe = SchedulePipeline(INPUT_DIM, bucket_policy=BucketPolicy())
    hook = ScriptedChaos(fail={"prefetch": [1]})
    with install_chaos(hook):
        packer = pipe.prefetch(iter(source), depth=2)
        got = [np.asarray(b.ext) for b in packer]
    assert packer.transient_retries == 1
    assert hook.fired["prefetch"] == [1]
    assert len(got) == len(want)
    for g, w in zip(got, want):                 # order + content preserved
        np.testing.assert_array_equal(g, w)


def test_prefetch_chaos_exhausts_retry_budget_and_surfaces():
    pipe = SchedulePipeline(INPUT_DIM, bucket_policy=BucketPolicy())
    # default retry budget is 2: three failures on the same item surface
    with install_chaos(ScriptedChaos(fail={"prefetch": [0, 1, 2]})):
        packer = pipe.prefetch(iter(_batch_stream(1, 2)), depth=1)
        with pytest.raises(SimulatedFailure):
            list(packer)


def test_persist_chaos_absorbed_as_miss_and_store_error(tmp_path):
    graphs, _ = _batch_stream(2, 1)[0]
    store = SchedulePersist(str(tmp_path))

    # store fault: swallowed (warn-once), counted, the BATCH entry never
    # lands.  Each store call is its own chaos site, so the token hits
    # the batch write and the harvested per-graph solos still land.
    cache = ScheduleCache(capacity=8, persist=store, splice=True)
    with install_chaos(ScriptedChaos(fail={"persist_store": [0]})):
        with pytest.warns(RuntimeWarning, match="cold packs"):
            sched, _ = cache.get_or_pack_device(graphs, None)
    assert store.store_errors == 1
    assert store.stores == cache.stats()["harvests"]
    assert store.load(batch_fingerprint(graphs)) is None
    assert sched is not None

    # The remaining phases exercise the BATCH disk tier in isolation —
    # splice pinned off, else the graph tier (seeded by the harvest
    # above) would serve every miss and the load-fault path under test
    # would never run.

    # fault-free repack from a fresh cache lands the entry on disk
    n = store.stores
    ScheduleCache(capacity=8, persist=store,
                  splice=False).get_or_pack_device(graphs, None)
    assert store.stores == n + 1

    # load fault on that real entry: counted miss, served by a cold pack
    misses_before = store.load_misses
    cold = ScheduleCache(capacity=8, persist=store, splice=False)
    with install_chaos(ScriptedChaos(fail={"persist_load": [0]})):
        cold.get_or_pack_device(graphs, None)
    assert store.load_misses == misses_before + 1
    assert cold.packs == 1 and cold.disk_hits == 0   # degraded to cold

    # without chaos the same entry is really readable (it was the
    # injection, not the store, that missed)
    fine = ScheduleCache(capacity=8, persist=store, splice=False)
    fine.get_or_pack_device(graphs, None)
    assert fine.disk_hits == 1 and fine.packs == 0


# ---------------------------------------------------------------------------
# Training under chaos ≡ fault-free training
# ---------------------------------------------------------------------------

def test_training_under_transient_chaos_is_bit_identical(tmp_path):
    """Prefetch retries and persist faults are ABSORBED: a training run
    whose pipeline is being actively faulted converges to the exact
    same final state as the fault-free run."""
    from repro.core.scheduler import execute, readout_roots
    from repro.train import MetricLogger, TrainConfig, Trainer

    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    source = _batch_stream(6, 12, bs=4)

    def init_params(key):
        return fn.init(key)

    def loss_fn(p, batch):
        buf = execute(fn, p, batch["dev"], batch["ext"],
                      fusion_mode="none").buf
        roots = readout_roots(buf, batch["dev"])
        l = jnp.mean(roots ** 2)
        return l, {"root_norm": l}

    def run(persist_dir, hook):
        pipe = SchedulePipeline(
            INPUT_DIM, bucket_policy=BucketPolicy(mode="pow2"),
            cache=ScheduleCache(capacity=32,
                                persist=SchedulePersist(persist_dir)))
        tr = Trainer(loss_fn, init_params,
                     TrainConfig(lr=0.02, warmup_steps=3, total_steps=12,
                                 weight_decay=0.0, log_every=100))
        state = tr.init_state(jax.random.PRNGKey(0))

        def stream():
            for pb in pipe.prefetch(iter(source), depth=2):
                yield {"dev": pb.dev, "ext": pb.ext}

        import contextlib
        ctx = install_chaos(hook) if hook else contextlib.nullcontext()
        with ctx:
            state, _ = tr.fit(state, stream(), steps=12,
                              logger=MetricLogger(log_fn=lambda *_: None))
        return jax.tree.map(np.asarray, state.params)

    clean = run(str(tmp_path / "clean"), None)
    hook = ScriptedChaos(fail={"prefetch": [0, 5],
                               "persist_store": [1],
                               "persist_load": [2]})
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)   # warn-once store
        chaotic = run(str(tmp_path / "chaos"), hook)
    assert hook.fired.get("prefetch") == [0, 5]
    jax.tree.map(np.testing.assert_array_equal, clean, chaotic)


# ---------------------------------------------------------------------------
# The lifecycle property: nothing lost, nothing duplicated, nothing wrong
# ---------------------------------------------------------------------------

_PROP_FN = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
_PROP_PARAMS = _PROP_FN.init(jax.random.PRNGKey(0))
_PROP_POOL = [chain(2), chain(4),
              random_binary_tree(5, np.random.default_rng(0))]


_PROP_ENG = [None]


def _prop_engine(clock, max_queue):
    """ONE engine shared across hypothesis examples (warm jit + schedule
    caches); each example gets a FRESH lifecycle/breaker — exactly the
    state under test."""
    if _PROP_ENG[0] is None:
        _PROP_ENG[0] = StructureServeEngine(
            _PROP_FN, _PROP_PARAMS, batch_size=2, compose=False,
            breaker_threshold=2)
    eng = _PROP_ENG[0]
    eng.lifecycle = RequestLifecycle(max_queue=max_queue, clock=clock)
    eng._breaker = CircuitBreaker(2)
    return eng


_PROP_REF = {}


def _prop_reference(which):
    """Fault-free input + root state of pool graph ``which``, scored
    alone.  NOTE: resets the shared engine's lifecycle — only call
    between examples' engine uses (the test warms all refs up front)."""
    if which not in _PROP_REF:
        g = _PROP_POOL[which]
        rng = np.random.default_rng(100 + which)
        x = (rng.standard_normal((g.num_nodes, INPUT_DIM))
             .astype(np.float32) * 0.3)
        eng = _prop_engine(lambda: 0.0, None)
        req = StructureRequest(request_id=0, graph=g, inputs=x)
        assert eng.submit(req)
        eng.run()
        assert req.status == OK
        _PROP_REF[which] = (x, req.root_state)
    return _PROP_REF[which]


def _check_interleaving(plan, max_queue, pack_faults, kernel_faults, hold):
    """The lifecycle property, checked for ONE interleaving: the
    multiset of terminal requests == the multiset submitted (each
    exactly once, each with a terminal status), rejected/timeout
    requests carry errors and no result, and every completed request's
    result matches the fault-free reference."""
    refs = {w for w, _, _ in plan}
    for w in refs:                               # warm BEFORE the engine
        _prop_reference(w)                       # reset below (shared)

    t = [0.0]
    eng = _prop_engine(lambda: t[0], max_queue)
    submitted = []
    for i, (which, ttl, valid) in enumerate(plan):
        x, _ = _prop_reference(which)
        if not valid:                            # malformed: extra row
            x = np.vstack([x, x[:1]])
        req = StructureRequest(request_id=i, graph=_PROP_POOL[which],
                               inputs=x, ttl=ttl)
        accepted = eng.submit(req)
        assert accepted == (req.status == "pending")
        submitted.append(req)
        t[0] += 0.5
    t[0] += hold                                 # ttl=2.0 may expire

    hook = ScriptedChaos(fail={"pack": pack_faults,
                               "kernel": kernel_faults})
    with install_chaos(hook):
        for _ in range(64):
            if eng.step() == 0:
                t[0] += 1.0
                if not eng.queue:
                    break
            t[0] += 1.0

    # -- nothing lost, nothing duplicated, everything terminal ---------
    assert not eng.queue
    assert sorted(r.request_id for r in eng.finished) == \
        sorted(r.request_id for r in submitted)
    assert len(eng.finished) == len(set(id(r) for r in eng.finished))
    for req in submitted:
        assert req.status in TERMINAL
        assert req.done

    # -- per-terminal contracts ----------------------------------------
    h = eng.health()
    by_status = {s: [r for r in submitted if r.status == s]
                 for s in TERMINAL}
    assert len(by_status[REJECTED]) == h["rejected"]
    assert len(by_status[OK]) == h["completed"]
    for req, (_, ttl, valid) in zip(submitted, plan):
        if not valid:
            assert req.status == REJECTED
            assert "input rows" in req.error
        if req.status != OK:
            assert req.root_state is None
            assert req.error is not None or req.status == "timeout"
        if req.status == "timeout":
            assert "deadline exceeded" in req.error

    # -- completed results match the fault-free reference --------------
    for req, (which, _, _) in zip(submitted, plan):
        if req.status == OK:
            _, want = _prop_reference(which)
            np.testing.assert_allclose(req.root_state, want,
                                       rtol=1e-4, atol=1e-5)


#: Hand-picked interleavings so the invariant is exercised even where
#: hypothesis is not installed: (plan, max_queue, pack_faults,
#: kernel_faults, hold) — plan rows are (pool_graph, ttl, valid).
_FIXED_CASES = [
    # deadlines: early submits expire while waiting, late ones complete
    ([(0, 2.0, True), (1, 2.0, True), (2, None, True), (0, 1e6, True)],
     None, set(), set(), 5.0),
    # backpressure + a persistent poison driven to a singleton by bisect
    ([(0, None, True), (1, None, True), (2, None, True),
      (0, None, True), (1, None, True)],
     3, {0, 1, 2}, set(), 0.0),
    # kernel failures past the breaker threshold + a malformed request
    ([(2, None, True), (0, None, False), (1, None, True),
      (2, None, True), (1, 2.0, True), (0, None, True)],
     None, set(), {0, 1, 2, 3, 4, 5, 6, 7}, 0.0),
    # everything at once: faults on both sites, cap, deadlines, garbage
    ([(0, 2.0, True), (1, None, False), (2, None, True),
      (0, None, True), (1, 1e6, True), (2, 2.0, True)],
     3, {0, 3}, {1, 2}, 5.0),
]


@pytest.mark.parametrize("case", range(len(_FIXED_CASES)))
def test_chaos_interleaving_fixed_cases(case):
    _check_interleaving(*_FIXED_CASES[case])


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_chaos_interleaving_preserves_lifecycle_invariants(data):
    """Randomized sweep over submits × deadlines × queue pressure ×
    injected pack/kernel faults (the fixed cases above, generalized)."""
    n = data.draw(st.integers(1, 6), label="n_requests")
    max_queue = data.draw(st.sampled_from([None, 3]), label="max_queue")
    plan = [(data.draw(st.integers(0, len(_PROP_POOL) - 1),
                       label=f"graph_{i}"),
             data.draw(st.sampled_from([None, 2.0, 1e6]),
                       label=f"ttl_{i}"),
             data.draw(st.booleans(), label=f"valid_{i}"))
            for i in range(n)]
    pack_faults = data.draw(st.sets(st.integers(0, 7), max_size=4),
                            label="pack_faults")
    kernel_faults = data.draw(st.sets(st.integers(0, 7), max_size=4),
                              label="kernel_faults")
    hold = data.draw(st.sampled_from([0.0, 5.0]), label="hold")
    _check_interleaving(plan, max_queue, pack_faults, kernel_faults, hold)
