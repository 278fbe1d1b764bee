"""The paper's core correctness claim: batched level-sync execution ≡
serial per-vertex execution ("Cavs produces exactly the same numerical
results", §5) — forward values AND parameter gradients, for arbitrary
random forests (hypothesis), plus the lazy-batching and streaming
(hoisting) equivalences of §3.5."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.hypothesis_compat import given, settings, st

from repro.core.scheduler import (execute, execute_lazy, execute_serial,
                                  readout_nodes, readout_roots)
from repro.core.structure import pack_batch, pack_external
from repro.models.rnn import GRUVertex, LSTMVertex
from repro.models.treelstm import TreeFCVertex, TreeLSTMVertex
from tests.test_structure import random_forest

VERTICES = {
    "lstm": lambda: LSTMVertex(input_dim=6, hidden=5),
    "gru": lambda: GRUVertex(input_dim=6, hidden=5),
    "treelstm": lambda: TreeLSTMVertex(input_dim=6, hidden=5, arity=8),
    "treefc": lambda: TreeFCVertex(input_dim=6, hidden=5, arity=8),
}


def _setup(seed, fn):
    rng = np.random.default_rng(seed)
    graphs = random_forest(seed)
    if fn.arity == 1:                      # chains only for unary cells
        from repro.core.structure import chain
        graphs = [chain(g.num_nodes) for g in graphs]
    params = fn.init(jax.random.PRNGKey(seed))
    arity = max(max(g.max_arity for g in graphs), fn.arity, 1)
    sched = pack_batch(graphs, pad_arity=arity)
    inputs = [rng.standard_normal((g.num_nodes, 6)).astype(np.float32) * 0.3
              for g in graphs]
    ext = jnp.asarray(pack_external(inputs, sched, 6))
    return graphs, params, sched, inputs, ext


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from(sorted(VERTICES)))
def test_batched_equals_serial(seed, vname):
    fn = VERTICES[vname]()
    graphs, params, sched, inputs, ext = _setup(seed, fn)
    res = execute(fn, params, sched.to_device(), ext)
    nodes = np.asarray(readout_nodes(res.buf, sched.to_device()))
    serial = execute_serial(fn, params, graphs, inputs)
    for k, g in enumerate(graphs):
        np.testing.assert_allclose(nodes[k, : g.num_nodes], serial[k],
                                   rtol=2e-5, atol=2e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_lazy_grads_equal_scan_grads(seed):
    """Lazy batching (§3.5) must be a pure scheduling change: parameter
    and input gradients identical to grad-through-scan."""
    fn = TreeLSTMVertex(input_dim=6, hidden=5, arity=8)
    graphs, params, sched, inputs, ext = _setup(seed, fn)
    dev = sched.to_device()

    def loss_scan(p, e):
        r = execute(fn, p, dev, e)
        return jnp.sum(readout_roots(r.buf, dev) ** 2)

    def loss_lazy(p, e):
        buf = execute_lazy(fn, p, e, dev)
        return jnp.sum(readout_roots(buf, dev) ** 2)

    g1 = jax.grad(loss_scan, argnums=(0, 1))(params, ext)
    g2 = jax.grad(loss_lazy, argnums=(0, 1))(params, ext)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g1, g2)


def test_hoisting_is_pure_scheduling():
    """Streaming/eager hoisting (§3.5) must not change values."""
    fn = LSTMVertex(input_dim=6, hidden=5)
    graphs, params, sched, inputs, ext = _setup(3, fn)
    dev = sched.to_device()
    r_on = execute(fn, params, dev, ext, hoist=True)
    r_off = execute(fn, params, dev, ext, hoist=False)
    np.testing.assert_allclose(np.asarray(r_on.buf), np.asarray(r_off.buf),
                               rtol=2e-5, atol=2e-5)


def test_gather_vjp_is_scatter():
    """§3.4: the cotangent that flows into the buffer rows equals the
    scatter of child-gradient contributions (checked numerically against
    finite differences on a tiny tree)."""
    fn = TreeFCVertex(input_dim=2, hidden=3)
    from repro.core.structure import from_parent_pointers
    g = from_parent_pointers([-1, 0, 0])   # root with two leaves
    params = fn.init(jax.random.PRNGKey(0))
    sched = pack_batch([g])
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 2)).astype(np.float32)
    ext = jnp.asarray(pack_external([x], sched, 2))
    dev = sched.to_device()

    def loss(e):
        r = execute(fn, params, dev, e)
        return jnp.sum(readout_roots(r.buf, dev) ** 2)

    g_auto = np.asarray(jax.grad(loss)(ext))
    # finite differences
    eps = 1e-3
    g_fd = np.zeros_like(g_auto)
    base = float(loss(ext))
    for i in range(ext.shape[0]):
        for j in range(ext.shape[1]):
            e2 = ext.at[i, j].add(eps)
            g_fd[i, j] = (float(loss(e2)) - base) / eps
    np.testing.assert_allclose(g_auto, g_fd, rtol=0.05, atol=5e-3)


def test_push_collection():
    """collect_push returns one row per slot, zeros on padding."""

    fn = TreeFCVertex(input_dim=2, hidden=3)

    @dataclasses.dataclass(frozen=True)
    class PushFC(TreeFCVertex):
        def apply(self, params, io):
            out = super().apply(params, io)
            return dataclasses.replace(out, push=out.state * 2.0)

    pfn = PushFC(input_dim=2, hidden=3)
    from repro.core.structure import chain
    graphs = [chain(3), chain(2)]
    params = pfn.init(jax.random.PRNGKey(0))
    sched = pack_batch(graphs, pad_arity=pfn.arity)
    x = [np.ones((3, 2), np.float32), np.ones((2, 2), np.float32)]
    ext = jnp.asarray(pack_external(x, sched, 2))
    dev = sched.to_device()
    res = execute(pfn, params, dev, ext, collect_push=True)
    assert res.pushed is not None
    assert res.pushed.shape[0] == sched.T * sched.M
    np.testing.assert_allclose(np.asarray(res.pushed),
                               2 * np.asarray(res.buf[:-1]), rtol=1e-6)


def test_sentinel_row_stays_zero():
    fn = LSTMVertex(input_dim=6, hidden=5)
    graphs, params, sched, inputs, ext = _setup(7, fn)
    res = execute(fn, params, sched.to_device(), ext)
    np.testing.assert_array_equal(np.asarray(res.buf[-1]),
                                  np.zeros(fn.state_dim, np.float32))


def test_dag_structure_multi_parent():
    """Fig. 2(d): general graphs — a vertex gathered by MULTIPLE parents
    (DAG, not tree).  The buffer/gather machinery must fan its state out
    to every parent, and its cotangent must accumulate from all of them."""
    from repro.core.structure import InputGraph

    # diamond: 0 -> (1, 2) -> 3   (3 gathers from both 1 and 2; both
    # gather the SAME child 0)
    g = InputGraph(children=[[], [0], [0], [1, 2]])
    fn = TreeFCVertex(input_dim=3, hidden=4)
    params = fn.init(jax.random.PRNGKey(0))
    sched = pack_batch([g], pad_arity=2)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 3)).astype(np.float32)
    ext = jnp.asarray(pack_external([x], sched, 3))
    dev = sched.to_device()

    res = execute(fn, params, dev, ext)
    serial = execute_serial(fn, params, [g], [x])
    nodes = np.asarray(readout_nodes(res.buf, dev))
    np.testing.assert_allclose(nodes[0, :4], serial[0], rtol=2e-5, atol=2e-5)

    # cotangent fan-in: node 0 feeds two parents -> its external grad
    # must be the SUM of both paths (checked vs finite differences)
    def loss(e):
        r = execute(fn, params, dev, e)
        return jnp.sum(readout_roots(r.buf, dev) ** 2)

    g_auto = np.asarray(jax.grad(loss)(ext))
    eps, base = 1e-3, float(loss(ext))
    for j in range(3):
        e2 = ext.at[0, j].add(eps)
        fd = (float(loss(e2)) - base) / eps
        np.testing.assert_allclose(g_auto[0, j], fd, rtol=0.05, atol=5e-3)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_graph_rnn_dags_batched_equals_serial(seed):
    """Fig. 2(d) at scale: random multi-parent DAGs through the batched
    scheduler ≡ serial reference (hypothesis sweep)."""
    from repro.core.structure import random_dag
    rng = np.random.default_rng(seed)
    graphs = [random_dag(int(rng.integers(2, 14)), rng, max_arity=3)
              for _ in range(3)]
    fn = TreeLSTMVertex(input_dim=5, hidden=4, arity=3)
    params = fn.init(jax.random.PRNGKey(seed))
    arity = max(max(g.max_arity for g in graphs), 1)
    sched = pack_batch(graphs, pad_arity=max(arity, 3))
    inputs = [rng.standard_normal((g.num_nodes, 5)).astype(np.float32) * 0.3
              for g in graphs]
    ext = jnp.asarray(pack_external(inputs, sched, 5))
    dev = sched.to_device()
    res = execute(fn, params, dev, ext)
    nodes = np.asarray(readout_nodes(res.buf, dev))
    serial = execute_serial(fn, params, graphs, inputs)
    for k, g in enumerate(graphs):
        np.testing.assert_allclose(nodes[k, : g.num_nodes], serial[k],
                                   rtol=2e-5, atol=2e-5)


def test_padded_megastep_skips_dead_blocks(monkeypatch):
    """Tree-LSTM batches padded 4x wide and 2x deep, as buckets pad
    them: the fused path's kernels (pallas, interpret mode) skip the
    blocks and levels that hold no vertex, yet the loss and gradients
    of ``execute_lazy`` match the op-by-op path, and its forward buffer
    is a scan of the flag-less kernel's, bit for bit."""
    from repro.core.structure import random_binary_tree
    from repro.core.vertex import get_gate_spec
    from repro.kernels import level_megastep as lm

    rng = np.random.default_rng(3)
    fn = TreeLSTMVertex(input_dim=4, hidden=4, arity=2)
    graphs = [random_binary_tree(n, rng) for n in (12, 9, 14)]
    tight = pack_batch(graphs, pad_arity=2)
    sched = pack_batch(graphs, pad_levels=2 * tight.T,
                       pad_width=4 * tight.M, pad_arity=2)
    dev = sched.to_device()
    T, M = sched.T, sched.M
    live = lm.block_live(sched.node_mask)
    assert live.shape[1] > 1 and 0 < live.sum() <= live.size // 4
    params = fn.init(jax.random.PRNGKey(3))
    inputs = [rng.standard_normal((g.num_nodes, 4)).astype(np.float32)
              * 0.3 for g in graphs]
    ext = jnp.asarray(pack_external(inputs, sched, 4))

    def loss(p, e, mode):
        buf = execute_lazy(fn, p, e, dev, fusion_mode=mode)
        return jnp.sum(readout_nodes(buf, dev) ** 2) \
            + jnp.sum(readout_roots(buf, dev) ** 3)

    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    l_fu, g_fu = jax.value_and_grad(loss, (0, 1))(params, ext, "megastep")
    buf_fu = execute_lazy(fn, params, ext, dev, fusion_mode="megastep")
    l_un, g_un = jax.value_and_grad(loss, (0, 1))(params, ext, "none")
    np.testing.assert_allclose(float(l_fu), float(l_un), rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g_fu, g_un)

    spec = get_gate_spec(fn)
    ext_rows = lm.as_rows(fn.project_inputs(params, ext))

    def step(buf, xs):
        t, cids, eids, nm = xs
        return lm.megastep(spec.kind, buf, cids, eids, nm, t * M, ext_rows,
                           spec.weights(params), interpret=True), None

    plain, _ = jax.lax.scan(
        step, jnp.zeros((T * M + 1, 1, spec.state_dim), jnp.float32),
        (jnp.arange(T, dtype=jnp.int32), dev.child_ids, dev.ext_ids,
         dev.node_mask))
    np.testing.assert_array_equal(np.asarray(buf_fu),
                                  np.asarray(lm.from_rows(plain)))
