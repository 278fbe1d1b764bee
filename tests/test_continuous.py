"""Continuous cross-request batching (serve.continuous): the engine's
contract is that interleaving NEVER changes results — for ANY sequence
of admissions, ticks, clock advances and TTLs, every request that
completes has a root state (and readout logits) BIT-IDENTICAL to
scoring that request alone through ``StructureServeEngine``, and every
submitted request reaches exactly one terminal status."""

import numpy as np
import pytest

import jax

from repro.core.structure import InputGraph, chain, random_dag
from repro.models.readout import ClassificationHead, TokenReadout
from repro.models.rnn import LSTMVertex
from repro.models.treelstm import TreeLSTMVertex
from repro.pipeline import ScheduleCache
from repro.serve import (AdmissionPolicy, ContinuousBatchEngine,
                         ContinuousRequest, StructureRequest,
                         StructureServeEngine, TERMINAL)

from tests.hypothesis_compat import given, settings, st

MODES = ["none", "megastep"]

_LSTM = LSTMVertex(input_dim=4, hidden=3)
_LSTM_PARAMS = _LSTM.init(jax.random.PRNGKey(0))
_TREE = TreeLSTMVertex(input_dim=4, hidden=3, arity=2)
_TREE_PARAMS = _TREE.init(jax.random.PRNGKey(1))


def _solo_root(fn, params, g, x, mode):
    """The bit-identity reference: the request scored ALONE through the
    structure engine (same bucket policy, same fusion leg)."""
    eng = StructureServeEngine(fn, params, batch_size=1, compose=False,
                               fusion_mode=mode)
    req = StructureRequest(0, g, x)
    assert eng.submit(req), req.error
    eng.run()
    assert req.status == "ok", (req.status, req.error)
    return req.root_state


def _mk_graph(fn, rng, n):
    arity = max(1, getattr(fn, "arity", 1))
    if arity == 1:
        return chain(n)
    return random_dag(n, rng, max_arity=arity)


def _mk_inputs(rng, g, input_dim):
    return rng.standard_normal((g.num_nodes, input_dim)) \
              .astype(np.float32) * 0.4


# ---------------------------------------------------------------------------
# The property: per-request bit-identity under ANY interleaving
# ---------------------------------------------------------------------------

def _run_interleaving(fn, params, mode, sizes, schedule, *, head=None,
                      head_params=None, ttls=None):
    """Drive the engine through an arbitrary interleaving of admissions
    / steps / clock advances (virtual clock) and return the requests."""
    t = [0.0]
    eng = ContinuousBatchEngine(
        fn, params, num_rows=32, frontier_width=3, fusion_mode=mode,
        clock=lambda: t[0], head=head, head_params=head_params,
        policy=AdmissionPolicy(min_occupancy=0.25, ttl_slack_s=0.05,
                               max_defer_ticks=2, max_window=4))
    rng = np.random.default_rng(hash(tuple(sizes)) % (2 ** 32))
    reqs = [ContinuousRequest(
        i, _mk_graph(fn, rng, n), None,
        ttl=None if ttls is None else ttls[i]) for i, n in enumerate(sizes)]
    for r in reqs:
        r.inputs = _mk_inputs(rng, r.graph, fn.input_dim)

    it = iter(reqs)
    for op in schedule:
        if op == "submit":
            nxt = next(it, None)
            if nxt is not None:
                eng.submit(nxt)
        elif op == "step":
            eng.step()
        elif op == "clock":
            t[0] += 0.2
    for nxt in it:                        # whatever the schedule didn't
        eng.submit(nxt)                   # submit goes in at the end
    eng.run()
    return eng, reqs


def _check_bit_identity(fn, params, mode, eng, reqs, head=None,
                        head_params=None):
    assert len(eng.finished) == len(reqs)
    for r in reqs:
        assert r.status in TERMINAL, r.status
        assert eng.finished.count(r) == 1     # exactly one terminal
        if r.status != "ok":
            continue
        solo = _solo_root(fn, params, r.graph, r.inputs, mode)
        np.testing.assert_array_equal(
            r.root_state, solo,
            err_msg=f"request {r.request_id} (mode={mode}) root state "
                    f"differs from solo scoring")
        if head is not None:
            want = np.asarray(head.logits(head_params,
                                          jax.numpy.asarray(solo[None])))[0]
            np.testing.assert_array_equal(r.logits, want)
    assert eng.num_active == 0 and not eng.queue
    assert eng.free_rows == eng.num_rows
    # Freed arena rows are re-zeroed (dead state never lingers).
    np.testing.assert_array_equal(np.asarray(eng._buf),
                                  np.zeros_like(np.asarray(eng._buf)))


@pytest.mark.parametrize("mode", MODES)
@settings(max_examples=15, deadline=None)
@given(st.data())
def test_property_any_interleaving_is_bit_identical(mode, data):
    sizes = data.draw(st.lists(st.integers(min_value=1, max_value=9),
                               min_size=1, max_size=6))
    schedule = data.draw(st.lists(
        st.sampled_from(["submit", "step", "clock"]),
        min_size=0, max_size=12))
    with_ttl = data.draw(st.booleans())
    ttls = None
    if with_ttl:
        ttls = [data.draw(st.sampled_from([None, 0.1, 1000.0]))
                for _ in sizes]
    eng, reqs = _run_interleaving(_LSTM, _LSTM_PARAMS, mode, sizes,
                                  schedule, ttls=ttls)
    _check_bit_identity(_LSTM, _LSTM_PARAMS, mode, eng, reqs)


@pytest.mark.parametrize("mode", MODES)
@settings(max_examples=10, deadline=None)
@given(st.data())
def test_property_tree_cohorts_bit_identical(mode, data):
    sizes = data.draw(st.lists(st.integers(min_value=1, max_value=11),
                               min_size=1, max_size=5))
    schedule = data.draw(st.lists(
        st.sampled_from(["submit", "step"]), min_size=0, max_size=10))
    eng, reqs = _run_interleaving(_TREE, _TREE_PARAMS, mode, sizes,
                                  schedule)
    _check_bit_identity(_TREE, _TREE_PARAMS, mode, eng, reqs)


# ---------------------------------------------------------------------------
# Fixed interleavings (run even without hypothesis)
# ---------------------------------------------------------------------------

_FIXED_CASES = [
    # (sizes, schedule, ttls)
    ([3, 7, 5, 12, 1, 9], [], None),                       # all up front
    ([6, 2, 8], ["submit", "step", "step", "submit", "step", "submit"],
     None),                                                # staggered
    ([4, 4, 4, 4, 4], ["submit", "submit", "step", "clock", "submit",
                       "step", "submit", "clock", "step"], None),
    ([9, 2, 7, 3], ["submit", "step", "clock", "clock", "submit", "step",
                    "submit", "clock", "step"],
     [1000.0, 0.1, None, 1000.0]),                         # mixed TTLs
]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", range(len(_FIXED_CASES)))
def test_fixed_interleavings_bit_identical(mode, case):
    sizes, schedule, ttls = _FIXED_CASES[case]
    head = ClassificationHead(_LSTM.state_dim, 3)
    hp = head.init(jax.random.PRNGKey(7))
    eng, reqs = _run_interleaving(_LSTM, _LSTM_PARAMS, mode, sizes,
                                  schedule, head=head, head_params=hp,
                                  ttls=ttls)
    _check_bit_identity(_LSTM, _LSTM_PARAMS, mode, eng, reqs,
                        head=head, head_params=hp)


# ---------------------------------------------------------------------------
# Lifecycle invariants under continuous admission
# ---------------------------------------------------------------------------

def test_exactly_one_terminal_under_churn():
    """Rejections (bad structure, arity overflow, double submit, full
    queue), timeouts, and completions each route every request to
    exactly one terminal — none lost, none counted twice."""
    t = [0.0]
    fn, params = _LSTM, _LSTM_PARAMS
    eng = ContinuousBatchEngine(fn, params, num_rows=8, frontier_width=2,
                                max_queue=2, clock=lambda: t[0],
                                policy=AdmissionPolicy(min_occupancy=0.0,
                                                       max_window=1))
    rng = np.random.default_rng(0)
    reqs = []

    ok = ContinuousRequest(0, chain(3), _mk_inputs(rng, chain(3), 4))
    assert eng.submit(ok)
    reqs.append(ok)

    bad = ContinuousRequest(1, chain(2), np.full((2, 4), np.nan,
                                                 np.float32))
    assert not eng.submit(bad) and bad.status == "rejected"
    reqs.append(bad)

    too_big = ContinuousRequest(2, chain(20), _mk_inputs(rng, chain(20), 4))
    assert not eng.submit(too_big) and too_big.status == "rejected"
    assert "arena rows" in too_big.error
    reqs.append(too_big)

    tree = random_dag(4, rng, max_arity=2)
    wide = ContinuousRequest(3, tree, _mk_inputs(rng, tree, 4))
    if tree.max_arity > 1:
        assert not eng.submit(wide) and wide.status == "rejected"
        assert "arity" in wide.error
        reqs.append(wide)

    slow = ContinuousRequest(4, chain(8), _mk_inputs(rng, chain(8), 4),
                             ttl=0.5)
    assert eng.submit(slow)
    reqs.append(slow)

    # Fill the bounded queue → backpressure rejection.
    fillers = [ContinuousRequest(10 + i, chain(2),
                                 _mk_inputs(rng, chain(2), 4))
               for i in range(4)]
    accepted = [eng.submit(f) for f in fillers]
    assert not all(accepted)              # at least one backpressured
    reqs.extend(fillers)

    # Double submit: the live object keeps its one lifecycle.
    assert not eng.submit(ok)

    eng.step()
    t[0] = 1.0                            # expire `slow` mid-flight
    eng.run()

    for r in reqs:
        assert r.status in TERMINAL, (r.request_id, r.status)
        assert eng.finished.count(r) == 1
    assert slow.status == "timeout"
    assert ok.status == "ok"
    # Every submitted object is in finished exactly once — the double
    # submit did NOT give `ok` a second lifecycle.
    assert sorted(id(r) for r in eng.finished) == \
        sorted(id(r) for r in reqs)


def test_degradation_ladder_and_double_failure():
    """Fused window failure degrades to the oracle (same results);
    both-rung failure fails the in-flight set and frees (zeroes) rows."""
    fn, params = _LSTM, _LSTM_PARAMS
    rng = np.random.default_rng(1)
    eng = ContinuousBatchEngine(fn, params, num_rows=8, frontier_width=2,
                                fusion_mode="megastep")
    r = ContinuousRequest(0, chain(5), _mk_inputs(rng, chain(5), 4))
    eng.submit(r)
    orig = eng._window
    eng._window = lambda *a: (_ for _ in ()).throw(RuntimeError("kaboom"))
    eng.run()
    eng._window = orig
    assert r.status == "ok"               # the oracle rung finished it
    assert eng.health()["degradations"] > 0
    assert eng.health()["last_degradation"] == "RuntimeError: kaboom"
    np.testing.assert_array_equal(
        r.root_state, _solo_root(fn, params, r.graph, r.inputs, "none"))

    eng2 = ContinuousBatchEngine(fn, params, num_rows=8, frontier_width=2,
                                 fusion_mode="none")
    r2 = ContinuousRequest(1, chain(5), _mk_inputs(rng, chain(5), 4))
    eng2.submit(r2)
    eng2._window_oracle = \
        lambda *a: (_ for _ in ()).throw(RuntimeError("kaboom"))
    eng2.run()
    assert r2.status == "failed"
    assert eng2.free_rows == eng2.num_rows
    np.testing.assert_array_equal(np.asarray(eng2._buf),
                                  np.zeros_like(np.asarray(eng2._buf)))


def test_token_generation_deterministic_across_interleavings():
    """Sampled-feedback generation keys on fold_in(rng, request_id):
    the SAME tokens come out whether the request ran alone or co-batched
    behind an arbitrary admission order."""
    fn, params = _LSTM, _LSTM_PARAMS
    rng = np.random.default_rng(2)
    tr = TokenReadout(fn, vocab=13)
    tp = tr.init(jax.random.PRNGKey(3))

    def run(extra_sizes):
        eng = ContinuousBatchEngine(fn, params, num_rows=32,
                                    frontier_width=3, token_readout=tr,
                                    token_params=tp, max_new_tokens=6,
                                    rng=jax.random.PRNGKey(9))
        g = chain(5)
        gen = np.random.default_rng(5)
        target = ContinuousRequest(77, g, _mk_inputs(gen, g, 4))
        eng.submit(target)
        for i, n in enumerate(extra_sizes):
            eng.submit(ContinuousRequest(
                i, chain(n), _mk_inputs(gen, chain(n), 4)))
        eng.run()
        assert target.status == "ok"
        return target.tokens

    alone = run([])
    crowded = run([3, 8, 2, 6])
    assert alone == crowded and len(alone) == 6


def test_poisson_trace_replay_is_bit_identical_to_solo():
    """Serving parity under load: 120 chain requests (4-32 tokens) on a
    seeded Poisson trace at 250 req/s, replayed in real time into a
    warmed engine, each finish with a root state bitwise equal to
    scoring the request alone."""
    import time

    lengths = (4, 6, 8, 12, 16, 24, 32)
    input_dim = 32
    fn = LSTMVertex(input_dim=input_dim, hidden=64)
    params = fn.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / 250.0, size=120))
    lens = rng.choice(np.asarray(lengths), size=120)
    eng = ContinuousBatchEngine(
        fn, params, num_rows=1024, frontier_width=64, fusion_mode="auto",
        policy=AdmissionPolicy(min_occupancy=0.0, max_window=8))
    warm_rng = np.random.default_rng(99)
    for j, n in enumerate(lengths):       # compile-warm preamble
        eng.submit(ContinuousRequest(
            1000 + j, chain(n), warm_rng.standard_normal(
                (n, input_dim)).astype(np.float32)))
    eng.run()
    x_rng = np.random.default_rng(1)
    reqs = [ContinuousRequest(
                i, chain(int(n)),
                x_rng.standard_normal((int(n), input_dim))
                .astype(np.float32) * 0.3)
            for i, n in enumerate(lens)]
    t0, i = time.monotonic(), 0
    while True:
        now = time.monotonic() - t0
        while i < len(reqs) and arrivals[i] <= now:
            eng.submit(reqs[i])
            i += 1
        live = eng.step()
        if i >= len(reqs) and live == 0:
            break
        if live == 0:
            time.sleep(min(0.001, max(0.0, arrivals[i]
                                      - (time.monotonic() - t0))))
        assert time.monotonic() - t0 < 300.0, "replay over its wall budget"
    assert all(r.status == "ok" for r in reqs)
    solo = StructureServeEngine(fn, params, batch_size=1, compose=False,
                                fusion_mode="auto")
    checks = [StructureRequest(r.request_id, r.graph, r.inputs)
              for r in reqs]
    for c in checks:
        assert solo.submit(c), c.error
    solo.run()
    for r, c in zip(reqs, checks):
        assert c.status == "ok", (c.status, c.error)
        assert np.array_equal(r.root_state, c.root_state), r.request_id


def test_plan_and_schedule_reuse_on_admission():
    """Recurring topologies admit through the cache's per-GRAPH tier —
    the pipeline satellite: admission does zero packing work on a hit,
    and the frontier plan memoized in the tier entry's extras rides
    along (plan lifetime == schedule lifetime, no private LRU)."""
    fn, params = _LSTM, _LSTM_PARAMS
    rng = np.random.default_rng(3)
    eng = ContinuousBatchEngine(fn, params, num_rows=64, frontier_width=4,
                                cache=ScheduleCache(enabled=True))
    for i in range(8):
        g = chain(5)                      # same topology every time
        assert eng.submit(ContinuousRequest(i, g, _mk_inputs(rng, g, 4)))
        eng.run()
    h = eng.health()
    assert h["plan_hits"] >= 7            # first admission is the miss
    assert h["plan_misses"] == 1
    stats = eng.cache.stats()
    assert stats["graph_hits"] >= 7       # served by the graph tier
    assert stats["graph_packs"] == 1      # one solo pack, ever


def test_disabled_cache_admission_replans_every_request():
    """A disabled cache really is uncached at admission: every submit
    re-packs and re-plans (one solo ``pack_batch`` each — never two),
    and serving still works."""
    fn, params = _LSTM, _LSTM_PARAMS
    rng = np.random.default_rng(4)
    eng = ContinuousBatchEngine(fn, params, num_rows=64, frontier_width=4,
                                cache=ScheduleCache(enabled=False))
    for i in range(3):
        g = chain(4)
        assert eng.submit(ContinuousRequest(i, g, _mk_inputs(rng, g, 4)))
        eng.run()
    h = eng.health()
    assert h["plan_misses"] == 3 and h["plan_hits"] == 0
    assert eng.cache.stats()["graph_packs"] == 3
