"""Fusion-aware vertex-function serving (serve.engine.VertexServeEngine):
the decode tick is one batching task routed through ``fusion_mode``, so
fused and op-by-op engines — and the training scheduler run over the
same chains — must agree on every request's final state, under slot
reuse and staggered admission."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.scheduler import execute, readout_nodes
from repro.core.structure import chain, pack_batch, pack_external
from repro.models.rnn import GRUVertex, LSTMVertex
from repro.models.treelstm import TreeLSTMVertex
from repro.serve import VertexRequest, VertexServeEngine


def _requests(rng, lens, input_dim):
    return [rng.standard_normal((L, input_dim)).astype(np.float32) * 0.3
            for L in lens]


def _scheduler_finals(fn, params, inputs):
    graphs = [chain(x.shape[0]) for x in inputs]
    sched = pack_batch(graphs)
    ext = jnp.asarray(pack_external(inputs, sched, fn.input_dim))
    dev = sched.to_device()
    buf = execute(fn, params, dev, ext, fusion_mode="none").buf
    nodes = np.asarray(readout_nodes(buf, dev))
    return [nodes[k, x.shape[0] - 1] for k, x in enumerate(inputs)]


@pytest.mark.parametrize("cell", [LSTMVertex, GRUVertex])
def test_decode_fused_equals_unfused_equals_scheduler(cell):
    fn = cell(input_dim=6, hidden=5)
    params = fn.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    lens = [3, 7, 1, 5, 4, 6]                 # 6 requests through 2 slots
    inputs = _requests(rng, lens, 6)

    finals = {}
    for mode in ("megastep", "none"):
        eng = VertexServeEngine(fn, params, num_slots=2, fusion_mode=mode)
        assert eng.fused == (mode == "megastep")
        for i, x in enumerate(inputs):
            eng.submit(VertexRequest(request_id=i, inputs=x))
        done = eng.run()
        assert len(done) == len(lens) and eng.num_active == 0
        finals[mode] = {r.request_id: r.final_state for r in done}

    oracle = _scheduler_finals(fn, params, inputs)
    for i in range(len(lens)):
        np.testing.assert_allclose(finals["megastep"][i], finals["none"][i],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(finals["megastep"][i], oracle[i],
                                   rtol=1e-4, atol=1e-5)


def test_decode_staggered_admission_slot_isolation():
    """A request's final state must not depend on its co-tenants or on
    WHEN it was admitted (continuous batching is pure data)."""
    fn = LSTMVertex(input_dim=4, hidden=3)
    params = fn.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    x0 = rng.standard_normal((6, 4)).astype(np.float32)

    def run(co_lens, co_at_tick):
        eng = VertexServeEngine(fn, params, num_slots=3)
        eng.submit(VertexRequest(request_id=0, inputs=x0))
        for _ in range(co_at_tick):
            eng.step()
        for i, L in enumerate(co_lens):
            eng.submit(VertexRequest(
                request_id=1 + i,
                inputs=rng.standard_normal((L, 4)).astype(np.float32)))
        done = eng.run()
        return {r.request_id: r.final_state for r in done}

    a = run(co_lens=[2, 9], co_at_tick=0)
    b = run(co_lens=[5], co_at_tick=3)
    np.testing.assert_array_equal(a[0], b[0])


def test_decode_respects_fusion_env():
    """``fusion_mode="none"`` forces the op-by-op tick, while "auto"
    fuses — the same contract as the training scheduler."""
    fn = GRUVertex(input_dim=4, hidden=3)
    params = fn.init(jax.random.PRNGKey(2))
    eng_auto = VertexServeEngine(fn, params, num_slots=2)
    assert eng_auto.fused
    eng_off = VertexServeEngine(fn, params, num_slots=2, fusion_mode="none")
    assert not eng_off.fused


def test_decode_rejects_tree_cells():
    fn = TreeLSTMVertex(input_dim=4, hidden=3, arity=2)
    with pytest.raises(ValueError, match="arity"):
        VertexServeEngine(fn, fn.init(jax.random.PRNGKey(0)), num_slots=2)


def test_timeout_freed_slot_rows_are_zeroed_before_reuse():
    """Regression: rows freed by the deadline sweep must be re-zeroed.

    Correctness never reads a freed slot's stale rows (a fresh admission
    gathers the zero SENTINEL at position 0), but a dead request's
    states must not linger in the pool — the invariant is that a slot
    freed by timeout or tick failure leaves BOTH its ping-pong rows
    exactly zero, and the next admission into it is bitwise what a
    fresh engine computes."""
    fn = LSTMVertex(input_dim=4, hidden=3)
    params = fn.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    long_x = rng.standard_normal((50, 4)).astype(np.float32)
    short_x = rng.standard_normal((5, 4)).astype(np.float32)

    t = [0.0]
    eng = VertexServeEngine(fn, params, num_slots=1, clock=lambda: t[0])
    victim = VertexRequest(request_id=0, inputs=long_x, ttl=0.5)
    assert eng.submit(victim)
    for _ in range(3):
        eng.step()                        # mid-flight: rows are non-zero
    assert float(np.abs(np.asarray(eng._buf)).max()) > 0.0
    t[0] = 1.0
    eng.step()                            # deadline sweep frees slot 0
    assert victim.status == "timeout"
    # Both ping-pong rows of the freed slot are exactly zero again
    # (row 2 is the sentinel, zero by construction).
    np.testing.assert_array_equal(np.asarray(eng._buf),
                                  np.zeros_like(np.asarray(eng._buf)))

    # Post-timeout admission sees a clean pool: bitwise equal to a
    # fresh engine scoring the same request.
    reused = VertexRequest(request_id=1, inputs=short_x)
    assert eng.submit(reused)
    eng.run()
    assert reused.status == "ok"

    fresh_eng = VertexServeEngine(fn, params, num_slots=1)
    fresh = VertexRequest(request_id=2, inputs=short_x)
    assert fresh_eng.submit(fresh)
    fresh_eng.run()
    np.testing.assert_array_equal(reused.final_state, fresh.final_state)


def test_tick_failure_zeroes_freed_slot_rows():
    """The other freeing path: a double-rung tick failure routes every
    in-flight request to ``failed`` — the vacated rows must be zero."""
    fn = LSTMVertex(input_dim=4, hidden=3)
    params = fn.init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(4)
    eng = VertexServeEngine(fn, params, num_slots=2, fusion_mode="none")
    eng.submit(VertexRequest(
        request_id=0,
        inputs=rng.standard_normal((6, 4)).astype(np.float32)))
    eng.step()                            # rows now hold live state

    # Break BOTH rungs for the next tick: oracle included.
    orig = eng._tick_oracle
    eng._tick_oracle = lambda *a: (_ for _ in ()).throw(RuntimeError("boom"))
    try:
        eng.step()
    finally:
        eng._tick_oracle = orig
    assert eng.finished[-1].status == "failed"
    np.testing.assert_array_equal(np.asarray(eng._buf),
                                  np.zeros_like(np.asarray(eng._buf)))
