"""Fused level-megastep equivalences (no hypothesis dependency — this
file must always collect and run):

  - fused ``execute``/``execute_lazy`` ≡ the op-by-op scan ≡
    ``execute_serial`` on forward states, for var-length chains
    (LSTM, GRU), random binary trees (Tree-LSTM, Tree-FC) and
    multi-parent DAGs (N-ary Tree-LSTM);
  - fused custom-VJP gradients (params AND external) ≡ grad through the
    unfused scan, to 1e-4;
  - the Pallas kernels (interpret mode) ≡ the ``ref.py`` oracle on a
    single batching task, including sentinel children, masked slots and
    in-place preservation of all untouched buffer rows;
  - the Pallas scatter-add backward (``level_megastep_bwd``) ≡ the jnp
    reverse sweep, standalone (duplicate indices) and end-to-end;
  - ``fusion_mode`` plumbing: "none" vs "megastep" vs "auto", the
    required-fusion error for cells without a GateSpec, and the
    fixed-arity fallback (Tree-FC on a mismatched schedule).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.scheduler import (execute, execute_lazy, execute_serial,
                                  readout_nodes, readout_roots)
from repro.core.structure import (chain, pack_batch, pack_external,
                                  random_binary_tree, random_dag)
from repro.core.vertex import LambdaVertex, VertexOutput
from repro.kernels import level_megastep as lm
from repro.kernels import level_megastep_bwd as lmb
from repro.kernels import ref
from repro.models.rnn import GRUVertex, LSTMVertex
from repro.models.treelstm import TreeFCVertex, TreeLSTMVertex


def _case(kind, seed, input_dim=6, hidden=5):
    rng = np.random.default_rng(seed)
    if kind == "lstm":
        fn = LSTMVertex(input_dim=input_dim, hidden=hidden)
        graphs = [chain(int(n)) for n in rng.integers(1, 12, size=4)]
    elif kind == "gru":
        fn = GRUVertex(input_dim=input_dim, hidden=hidden)
        graphs = [chain(int(n)) for n in rng.integers(1, 12, size=4)]
    elif kind == "treelstm":
        fn = TreeLSTMVertex(input_dim=input_dim, hidden=hidden, arity=2)
        graphs = [random_binary_tree(int(n), rng)
                  for n in rng.integers(1, 10, size=4)]
    elif kind == "treefc":
        fn = TreeFCVertex(input_dim=input_dim, hidden=hidden)
        graphs = [random_binary_tree(int(n), rng)
                  for n in rng.integers(1, 10, size=4)]
    else:  # multi-parent DAGs (Fig. 2d) through the N-ary cell
        fn = TreeLSTMVertex(input_dim=input_dim, hidden=hidden, arity=3)
        graphs = [random_dag(int(n), rng, max_arity=3)
                  for n in rng.integers(2, 12, size=3)]
    params = fn.init(jax.random.PRNGKey(seed))
    arity = max(max(g.max_arity for g in graphs), fn.arity, 1)
    sched = pack_batch(graphs, pad_arity=arity)
    inputs = [rng.standard_normal((g.num_nodes, input_dim)).astype(np.float32)
              * 0.3 for g in graphs]
    ext = jnp.asarray(pack_external(inputs, sched, input_dim))
    return fn, params, graphs, inputs, sched, ext


KINDS = ["lstm", "gru", "treelstm", "treefc", "dag"]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("kind", KINDS)
def test_fused_forward_equals_unfused_and_serial(kind, seed):
    fn, params, graphs, inputs, sched, ext = _case(kind, seed)
    dev = sched.to_device()
    r_un = execute(fn, params, dev, ext, fusion_mode="none")
    r_fu = execute(fn, params, dev, ext, fusion_mode="megastep")
    np.testing.assert_allclose(np.asarray(r_fu.buf), np.asarray(r_un.buf),
                               rtol=1e-4, atol=1e-5)
    nodes = np.asarray(readout_nodes(r_fu.buf, dev))
    serial = execute_serial(fn, params, graphs, inputs)
    for k, g in enumerate(graphs):
        np.testing.assert_allclose(nodes[k, : g.num_nodes], serial[k],
                                   rtol=2e-5, atol=2e-5)
    # the sentinel row is never written by any megastep
    np.testing.assert_array_equal(np.asarray(r_fu.buf[-1]), 0.0)


@pytest.mark.parametrize("seed", [0, 3])
@pytest.mark.parametrize("kind", KINDS)
def test_fused_grads_equal_unfused(kind, seed):
    """The fused custom VJP (scatter-add sweep + flat lazy param pass)
    must match grad-through-scan on params and external inputs."""
    fn, params, _, _, sched, ext = _case(kind, seed)
    dev = sched.to_device()

    def loss(p, e, mode):
        r = execute(fn, p, dev, e, fusion_mode=mode)
        return jnp.sum(readout_roots(r.buf, dev) ** 2)

    g_un = jax.grad(lambda p, e: loss(p, e, "none"), (0, 1))(params, ext)
    g_fu = jax.grad(lambda p, e: loss(p, e, "megastep"), (0, 1))(params, ext)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g_un, g_fu)


@pytest.mark.parametrize("kind", ["lstm", "gru", "treelstm", "treefc"])
def test_fused_lazy_matches_opbyop_lazy(kind):
    fn, params, _, _, sched, ext = _case(kind, 5)
    dev = sched.to_device()
    b_un = execute_lazy(fn, params, ext, dev, fusion_mode="none")
    b_fu = execute_lazy(fn, params, ext, dev, fusion_mode="megastep")
    np.testing.assert_allclose(np.asarray(b_fu), np.asarray(b_un),
                               rtol=1e-4, atol=1e-5)

    def loss(p, e, mode):
        return jnp.sum(readout_roots(
            execute_lazy(fn, p, e, dev, fusion_mode=mode), dev) ** 2)

    g_un = jax.grad(lambda p, e: loss(p, e, "none"), (0, 1))(params, ext)
    g_fu = jax.grad(lambda p, e: loss(p, e, "megastep"), (0, 1))(params, ext)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g_un, g_fu)


def test_fused_jit_roundtrip():
    """The fused path must trace/jit cleanly (scan-carried buffer)."""
    fn, params, _, _, sched, ext = _case("treelstm", 7)
    dev = sched.to_device()
    f = jax.jit(lambda p, e: execute(fn, p, dev, e,
                                     fusion_mode="megastep").buf)
    g = jax.jit(lambda p, e: execute(fn, p, dev, e, fusion_mode="none").buf)
    np.testing.assert_allclose(np.asarray(f(params, ext)),
                               np.asarray(g(params, ext)),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Pallas kernels (interpret mode) vs ref oracle
# ---------------------------------------------------------------------------

def _kernel(kind, buf, cids, eids, nm, off, ext, weights):
    """The Pallas megastep (interpret mode) on ``[R, S]`` operands: the
    kernel itself works in the row layout ``[R, 1, S]``."""
    out = lm.megastep(kind, lm.as_rows(buf), cids, eids, nm, jnp.int32(off),
                      lm.as_rows(ext), weights, interpret=True)
    return lm.from_rows(out)


def _level_fixture(seed, M=6, H=8, T=4, A=1, n_ext=10):
    rng = np.random.default_rng(seed)
    S = 2 * H
    buf = rng.standard_normal((T * M + 1, S)).astype(np.float32)
    buf[-1] = 0.0                                 # sentinel row
    t = 2
    cids = rng.integers(0, t * M, size=(M, A)).astype(np.int32)
    cids[0, -1] = T * M                           # one sentinel child
    cmask = (cids != T * M).astype(np.float32)
    eids = rng.integers(0, n_ext, size=(M,)).astype(np.int32)
    ext = rng.standard_normal((n_ext + 1, 4 * H)).astype(np.float32)
    nm = np.ones((M,), np.float32)
    nm[-1] = 0.0                                  # one padded slot
    return (jnp.asarray(buf), jnp.asarray(cids), jnp.asarray(cmask),
            jnp.asarray(eids), jnp.asarray(nm), t * M, jnp.asarray(ext), rng)


@pytest.mark.parametrize("seed,m,h", [(0, 6, 8), (1, 3, 16), (2, 9, 4)])
def test_lstm_megastep_kernel_matches_ref(seed, m, h):
    buf, cids, cmask, eids, nm, off, ext, rng = _level_fixture(seed, M=m, H=h)
    wh = jnp.asarray(rng.standard_normal((h, 4 * h)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal((4 * h,)) * 0.1, jnp.float32)
    out_p = _kernel("lstm", buf, cids, eids, nm, off, ext, (wh, b))
    out_r = ref.level_megastep("lstm", buf, cids, cmask, eids, nm, off, ext,
                               (wh, b))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-6, atol=2e-6)
    # in-place alias: every row outside [off, off+m) is preserved bit-exact
    np.testing.assert_array_equal(np.asarray(out_p[:off]),
                                  np.asarray(buf[:off]))
    np.testing.assert_array_equal(np.asarray(out_p[off + m:]),
                                  np.asarray(buf[off + m:]))


@pytest.mark.parametrize("seed,m,h,a", [(0, 6, 8, 2), (1, 5, 4, 3)])
def test_treelstm_megastep_kernel_matches_ref(seed, m, h, a):
    buf, cids, cmask, eids, nm, off, ext, rng = _level_fixture(
        seed, M=m, H=h, A=a)
    ws = [jnp.asarray(rng.standard_normal((h, h)) * 0.2, jnp.float32)
          for _ in range(4)]
    b = jnp.asarray(rng.standard_normal((4 * h,)) * 0.1, jnp.float32)
    out_p = _kernel("treelstm", buf, cids, eids, nm, off, ext,
                    tuple(ws) + (b,))
    out_r = ref.level_megastep("treelstm", buf, cids, cmask, eids, nm, off,
                               ext, tuple(ws) + (b,))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(out_p[:off]),
                                  np.asarray(buf[:off]))


@pytest.mark.parametrize("seed,m,h", [(0, 6, 8), (1, 3, 16)])
def test_gru_megastep_kernel_matches_ref(seed, m, h):
    rng = np.random.default_rng(seed)
    T, A = 4, 1
    buf = rng.standard_normal((T * m + 1, h)).astype(np.float32)
    buf[-1] = 0.0
    t = 2
    cids = rng.integers(0, t * m, size=(m, A)).astype(np.int32)
    cids[0, -1] = T * m                           # one sentinel child
    cmask = (cids != T * m).astype(np.float32)
    eids = rng.integers(0, 10, size=(m,)).astype(np.int32)
    ext = jnp.asarray(rng.standard_normal((11, 3 * h)), jnp.float32)
    nm = np.ones((m,), np.float32)
    nm[-1] = 0.0
    wh = jnp.asarray(rng.standard_normal((h, 3 * h)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal((3 * h,)) * 0.1, jnp.float32)
    out_p = _kernel("gru", jnp.asarray(buf), jnp.asarray(cids),
                    jnp.asarray(eids), jnp.asarray(nm), t * m, ext, (wh, b))
    out_r = ref.level_megastep("gru", jnp.asarray(buf), jnp.asarray(cids),
                               jnp.asarray(cmask), jnp.asarray(eids),
                               jnp.asarray(nm), t * m, ext, (wh, b))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(out_p[:t * m]), buf[:t * m])
    np.testing.assert_array_equal(np.asarray(out_p[t * m + m:]),
                                  buf[t * m + m:])


@pytest.mark.parametrize("seed,m,h,a", [(0, 6, 8, 2), (1, 5, 4, 3)])
def test_treefc_megastep_kernel_matches_ref(seed, m, h, a):
    rng = np.random.default_rng(seed)
    T = 4
    buf = rng.standard_normal((T * m + 1, h)).astype(np.float32)
    buf[-1] = 0.0
    t = 2
    cids = rng.integers(0, t * m, size=(m, a)).astype(np.int32)
    cids[0, -1] = T * m
    cmask = (cids != T * m).astype(np.float32)
    eids = rng.integers(0, 10, size=(m,)).astype(np.int32)
    ext = jnp.asarray(rng.standard_normal((11, h)), jnp.float32)
    nm = np.ones((m,), np.float32)
    nm[-1] = 0.0
    wc = jnp.asarray(rng.standard_normal((a * h, h)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal((h,)) * 0.1, jnp.float32)
    out_p = _kernel("treefc", jnp.asarray(buf), jnp.asarray(cids),
                    jnp.asarray(eids), jnp.asarray(nm), t * m, ext, (wc, b))
    out_r = ref.level_megastep("treefc", jnp.asarray(buf), jnp.asarray(cids),
                               jnp.asarray(cmask), jnp.asarray(eids),
                               jnp.asarray(nm), t * m, ext, (wc, b))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(out_p[:t * m]), buf[:t * m])
    np.testing.assert_array_equal(np.asarray(out_p[t * m + m:]),
                                  buf[t * m + m:])


# ---------------------------------------------------------------------------
# Pallas scatter-add backward (level_megastep_bwd) vs jnp reverse sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,r,d,n", [(0, 20, 10, 16), (1, 9, 130, 5),
                                        (2, 33, 256, 40)])
def test_scatter_add_rows_kernel_matches_ref(seed, r, d, n):
    """The backward memory op: duplicates must accumulate (∂gather =
    scatter-add for multi-parent DAGs), untouched rows preserved."""
    rng = np.random.default_rng(seed)
    dst = rng.standard_normal((r, d)).astype(np.float32)
    idx = rng.integers(0, r, size=(n,)).astype(np.int32)
    idx[n // 2] = idx[0]                          # force a duplicate
    rows = rng.standard_normal((n, d)).astype(np.float32)
    out_p = lmb.scatter_add_rows(jnp.asarray(dst), jnp.asarray(idx),
                                 jnp.asarray(rows), interpret=True)
    out_r = ref.scatter_add_rows(jnp.asarray(dst), jnp.asarray(idx),
                                 jnp.asarray(rows))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-6)
    untouched = np.setdiff1d(np.arange(r), idx)
    np.testing.assert_array_equal(np.asarray(out_p)[untouched],
                                  dst[untouched])


@pytest.mark.parametrize("kind", ["lstm", "gru", "treelstm", "treefc", "dag"])
def test_pallas_backward_matches_jnp_sweep(kind, monkeypatch):
    """End-to-end: the fused backward with the PALLAS scatter-add kernel
    (interpret mode) ≡ the same sweep through XLA's .at[].add oracle.
    The DAG case exercises duplicate child indices within one level."""
    fn, params, _, _, sched, ext = _case(kind, 17, input_dim=4, hidden=4)
    dev = sched.to_device()

    def loss(p, e):
        r = execute(fn, p, dev, e, fusion_mode="megastep")
        return jnp.sum(readout_roots(r.buf, dev) ** 2)

    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    g_pal = jax.grad(loss, (0, 1))(params, ext)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "chunked")
    g_jnp = jax.grad(loss, (0, 1))(params, ext)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g_pal, g_jnp)


def test_scheduler_pallas_megastep_matches_unfused(monkeypatch):
    """End-to-end: the scheduler's fused scan with the PALLAS backend
    (interpret mode on CPU) ≡ the unfused op-by-op scan."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    fn, params, _, _, sched, ext = _case("treelstm", 11, input_dim=4,
                                         hidden=4)
    dev = sched.to_device()
    r_fu = execute(fn, params, dev, ext, fusion_mode="megastep")
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "chunked")
    r_un = execute(fn, params, dev, ext, fusion_mode="none")
    np.testing.assert_allclose(np.asarray(r_fu.buf), np.asarray(r_un.buf),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fusion_mode plumbing
# ---------------------------------------------------------------------------

def test_fusion_mode_auto_uses_megastep_and_env_disables(monkeypatch):
    fn, params, _, _, sched, ext = _case("lstm", 13)
    dev = sched.to_device()
    r_auto = execute(fn, params, dev, ext)                  # default: auto
    monkeypatch.setenv("REPRO_FUSION", "none")
    r_env_off = execute(fn, params, dev, ext)
    np.testing.assert_allclose(np.asarray(r_auto.buf),
                               np.asarray(r_env_off.buf),
                               rtol=1e-5, atol=1e-6)


def test_fusion_mode_megastep_requires_gate_spec():
    # A cell with no gate_spec() declaration stays on the op-by-op path.
    fn = LambdaVertex(
        state_dim=3, ext_dim=2, arity=1,
        init_fn=lambda rng: {"w": jnp.zeros((2, 3))},
        apply_fn=lambda p, io: VertexOutput(state=io.pull() @ p["w"]),
        project_fn=lambda p, raw: raw)
    params = fn.init(jax.random.PRNGKey(0))
    sched = pack_batch([chain(3)], pad_arity=2)
    ext = jnp.asarray(pack_external([np.ones((3, 2), np.float32)], sched, 2))
    dev = sched.to_device()
    with pytest.raises(ValueError, match="GateSpec"):
        execute(fn, params, dev, ext, fusion_mode="megastep")
    # hoist=False also disqualifies the fused path
    fn2 = LSTMVertex(input_dim=2, hidden=3)
    with pytest.raises(ValueError, match="hoist"):
        execute(fn2, fn2.init(jax.random.PRNGKey(0)), dev,
                jnp.zeros((4, 2)), hoist=False, fusion_mode="megastep")


def test_fusion_mode_treefc_arity_mismatch(monkeypatch):
    """Tree-FC's concat weight fixes the gather arity: a schedule packed
    at a different A must raise under "megastep" and resolve to the
    op-by-op path (spec None) under "auto"."""
    from repro.core.scheduler import resolve_fusion
    monkeypatch.delenv("REPRO_FUSION", raising=False)   # CI matrix sets it
    fn = TreeFCVertex(input_dim=2, hidden=3)          # arity 2
    params = fn.init(jax.random.PRNGKey(0))
    sched = pack_batch([chain(3)])                    # chains pack at A=1
    ext = jnp.asarray(pack_external([np.ones((3, 2), np.float32)], sched, 2))
    dev = sched.to_device()
    with pytest.raises(ValueError, match="arity"):
        execute(fn, params, dev, ext, fusion_mode="megastep")
    assert resolve_fusion(fn, "auto", sched_arity=1) is None
    assert resolve_fusion(fn, "auto", sched_arity=2) is not None
    assert resolve_fusion(fn, "auto", sched_arity=2).kind == "treefc"
