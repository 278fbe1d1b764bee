"""Property-based gradient-parity harness for the fused backward
level-megastep (PR 3 tentpole).

Three independent renderings of the megastep reverse sweep must agree
on every cotangent — parameters, external inputs, and the state chain
(exercised all the way down to the leaf/initial levels by losses over
ALL node states, not just roots):

  1. ``fusion_mode="none"``        — op-by-op grad-through-scan (the
                                     dynamic-declaration oracle);
  2. fused VJP, ``chunked`` impl   — the jnp ``level_bwd`` sweep + XLA
                                     scatter-add (the pre-fusion path,
                                     kept as the ablation baseline);
  3. fused VJP, ``pallas`` impl    — ONE ``bwd_megastep`` launch per
                                     reverse level (interpret mode):
                                     recompute + cotangent math +
                                     duplicate-safe scatter-add fused,
                                     gradient buffer aliased in place.

The sweep is hypothesis-driven over random topologies (var-length
chains, random trees, multi-parent DAGs with duplicate child ids,
singleton levels, ``M=1``) for all four gate kinds, with deterministic
parametrized cases mirroring every topology class so the suite keeps
its coverage when hypothesis is not installed.

Also here: the analytic ``level_bwd``/``level_param_grads`` vs the pure
autodiff oracle (``ref.level_bwd``), the fused kernel vs the ref
reverse step on one level, the row-chunked scatter-add (duplicate
accumulation across panel boundaries), the structural launch census
(exactly one ``pallas_call`` in the forward scan body and one in the
reverse scan body), and the ``fusion_mode="megastep"`` error paths with
their raised MESSAGES asserted.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.hypothesis_compat import given, settings, st

from repro.core.scheduler import (execute, execute_lazy, readout_nodes,
                                  readout_roots)
from repro.core.structure import (chain, pack_batch, pack_external,
                                  random_binary_tree, random_dag)
from repro.core.vertex import LambdaVertex, VertexOutput, get_gate_spec
from repro.kernels import level_megastep as lm
from repro.kernels import level_megastep_bwd as lmb
from repro.kernels import ref
from repro.models.rnn import GRUVertex, LSTMVertex
from repro.models.treelstm import TreeFCVertex, TreeLSTMVertex

KINDS = ["lstm", "gru", "treelstm", "treefc", "dag"]


def _make_case(kind, seed, sizes=None, input_dim=4, hidden=4):
    """Pack a batch of random topologies for one gate kind.

    ``sizes``: per-graph node counts; defaults to a var-length draw.
    ``dag`` runs the N-ary Tree-LSTM over multi-parent DAGs — the
    topology class where one level scatters DUPLICATE child ids.
    """
    rng = np.random.default_rng(seed)
    if sizes is None:
        sizes = [int(n) for n in rng.integers(1, 9, size=3)]
    if kind == "lstm":
        fn = LSTMVertex(input_dim=input_dim, hidden=hidden)
        graphs = [chain(n) for n in sizes]
    elif kind == "gru":
        fn = GRUVertex(input_dim=input_dim, hidden=hidden)
        graphs = [chain(n) for n in sizes]
    elif kind == "treelstm":
        fn = TreeLSTMVertex(input_dim=input_dim, hidden=hidden, arity=2)
        graphs = [random_binary_tree(n, rng) for n in sizes]
    elif kind == "treefc":
        fn = TreeFCVertex(input_dim=input_dim, hidden=hidden)
        graphs = [random_binary_tree(n, rng) for n in sizes]
    else:
        fn = TreeLSTMVertex(input_dim=input_dim, hidden=hidden, arity=3)
        graphs = [random_dag(max(n, 2), rng, max_arity=3) for n in sizes]
    params = fn.init(jax.random.PRNGKey(seed))
    arity = max(max(g.max_arity for g in graphs), fn.arity, 1)
    sched = pack_batch(graphs, pad_arity=arity)
    inputs = [rng.standard_normal((g.num_nodes, input_dim)).astype(np.float32)
              * 0.3 for g in graphs]
    ext = jnp.asarray(pack_external(inputs, sched, input_dim))
    return fn, params, sched.to_device(), ext


def _grads(fn, params, dev, ext, mode, impl, monkeypatch, lazy=False):
    """Params + external cotangents under one (fusion_mode, impl) pair,
    with a loss over ALL node states — every buffer row, including the
    leaf (initial-state) levels, carries a nonzero cotangent."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)

    def loss(p, e):
        if lazy:
            buf = execute_lazy(fn, p, e, dev, fusion_mode=mode)
        else:
            buf = execute(fn, p, dev, e, fusion_mode=mode).buf
        nodes = readout_nodes(buf, dev)
        return jnp.sum(nodes ** 2) + jnp.sum(readout_roots(buf, dev) ** 3)

    return jax.grad(loss, (0, 1))(params, ext)


def _assert_tree_close(a, b, rtol=1e-4, atol=1e-5):
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=rtol, atol=atol), a, b)


# ---------------------------------------------------------------------------
# Gradient parity: fused pallas ≡ jnp level_bwd sweep ≡ op-by-op
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("kind", KINDS)
def test_bwd_parity_var_length(kind, seed, monkeypatch):
    fn, params, dev, ext = _make_case(kind, seed)
    g_none = _grads(fn, params, dev, ext, "none", "chunked", monkeypatch)
    g_jnp = _grads(fn, params, dev, ext, "megastep", "chunked", monkeypatch)
    g_pal = _grads(fn, params, dev, ext, "megastep", "pallas", monkeypatch)
    _assert_tree_close(g_none, g_jnp)
    _assert_tree_close(g_jnp, g_pal)


@pytest.mark.parametrize("kind", ["lstm", "treelstm"])
def test_bwd_parity_singleton_levels_and_m1(kind, monkeypatch):
    """A single chain packs at M=1 — every batching task is a singleton
    (the degenerate schedule the kernel's sorted-run grid must survive:
    n = A contributions, one run each).  The Tree-LSTM variant runs the
    N-ary child-sum cell over the same chain (arity padded to 2, so one
    real + one sentinel child per level)."""
    input_dim = 4
    if kind == "lstm":
        fn = LSTMVertex(input_dim=input_dim, hidden=4)
    else:
        fn = TreeLSTMVertex(input_dim=input_dim, hidden=4, arity=2)
    graphs = [chain(6)]
    params = fn.init(jax.random.PRNGKey(11))
    sched = pack_batch(graphs, pad_arity=max(fn.arity, 1))
    rng = np.random.default_rng(11)
    inputs = [rng.standard_normal((6, input_dim)).astype(np.float32) * 0.3]
    ext = jnp.asarray(pack_external(inputs, sched, input_dim))
    dev = sched.to_device()
    assert dev.M == 1
    g_none = _grads(fn, params, dev, ext, "none", "chunked", monkeypatch)
    g_pal = _grads(fn, params, dev, ext, "megastep", "pallas", monkeypatch)
    _assert_tree_close(g_none, g_pal)


def test_bwd_parity_single_vertex_graphs(monkeypatch):
    """Graphs of one node: T=1, leaves only, every child is the
    sentinel — the reverse sweep is pure seeding, no real scatter."""
    fn, params, dev, ext = _make_case("lstm", 3, sizes=[1, 1, 1])
    assert dev.T == 1
    g_none = _grads(fn, params, dev, ext, "none", "chunked", monkeypatch)
    g_pal = _grads(fn, params, dev, ext, "megastep", "pallas", monkeypatch)
    _assert_tree_close(g_none, g_pal)


@pytest.mark.parametrize("seed", [2, 9])
def test_bwd_parity_duplicate_child_ids(seed, monkeypatch):
    """Multi-parent DAGs: several parents in ONE level gather the same
    child row, so the fused kernel's sorted-run scatter must accumulate
    duplicates exactly like XLA's .at[].add."""
    fn, params, dev, ext = _make_case("dag", seed, sizes=[8, 10, 6])
    cids = np.asarray(dev.child_ids).reshape(dev.T, -1)
    has_dup = any(
        len(np.unique(r[r != dev.T * dev.M])) < np.sum(r != dev.T * dev.M)
        for r in cids)
    assert has_dup, "case must exercise duplicate child ids"
    g_none = _grads(fn, params, dev, ext, "none", "chunked", monkeypatch)
    g_pal = _grads(fn, params, dev, ext, "megastep", "pallas", monkeypatch)
    _assert_tree_close(g_none, g_pal)


@pytest.mark.parametrize("kind", ["gru", "treefc"])
def test_bwd_parity_execute_lazy(kind, monkeypatch):
    """The lazy entry point shares the fused VJP — same parity holds."""
    fn, params, dev, ext = _make_case(kind, 5)
    g_none = _grads(fn, params, dev, ext, "none", "chunked", monkeypatch,
                    lazy=True)
    g_pal = _grads(fn, params, dev, ext, "megastep", "pallas", monkeypatch,
                   lazy=True)
    _assert_tree_close(g_none, g_pal)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000),
       st.sampled_from(KINDS),
       st.lists(st.integers(1, 12), min_size=1, max_size=4))
def test_bwd_parity_property(seed, kind, sizes):
    """Hypothesis sweep: ANY random topology batch must satisfy the
    three-way gradient parity (fused pallas ≡ jnp sweep ≡ op-by-op)."""
    import os
    fn, params, dev, ext = _make_case(kind, seed, sizes=sizes)

    def loss(p, e, mode):
        buf = execute(fn, p, dev, e, fusion_mode=mode).buf
        return jnp.sum(readout_nodes(buf, dev) ** 2)

    old = os.environ.get("REPRO_KERNEL_IMPL")
    try:
        os.environ["REPRO_KERNEL_IMPL"] = "chunked"
        g_none = jax.grad(lambda p, e: loss(p, e, "none"), (0, 1))(params, ext)
        g_jnp = jax.grad(
            lambda p, e: loss(p, e, "megastep"), (0, 1))(params, ext)
        os.environ["REPRO_KERNEL_IMPL"] = "pallas"
        g_pal = jax.grad(
            lambda p, e: loss(p, e, "megastep"), (0, 1))(params, ext)
    finally:
        if old is None:
            os.environ.pop("REPRO_KERNEL_IMPL", None)
        else:
            os.environ["REPRO_KERNEL_IMPL"] = old
    _assert_tree_close(g_none, g_jnp)
    _assert_tree_close(g_jnp, g_pal)


# ---------------------------------------------------------------------------
# Analytic backward vs pure-autodiff oracle (one level, no scheduler)
# ---------------------------------------------------------------------------

def _level_case(kind, seed, m=5, h=4, a=None):
    rng = np.random.default_rng(seed)
    smult = {"lstm": 2, "treelstm": 2, "gru": 1, "treefc": 1}[kind]
    gmult = {"lstm": 4, "treelstm": 4, "gru": 3, "treefc": 1}[kind]
    a = a if a is not None else (1 if kind in ("lstm", "gru") else 2)
    S, G = smult * h, gmult * h
    child = rng.standard_normal((m, a, S)).astype(np.float32)
    cmask = (rng.random((m, a)) > 0.25).astype(np.float32)
    child *= cmask[..., None]          # masked children gather zeros
    rows = rng.standard_normal((m, G)).astype(np.float32)
    g_state = rng.standard_normal((m, S)).astype(np.float32)
    if kind in ("lstm", "gru"):
        ws = (rng.standard_normal((h, G)).astype(np.float32) * 0.3,
              rng.standard_normal((G,)).astype(np.float32) * 0.1)
    elif kind == "treelstm":
        ws = tuple(rng.standard_normal((h, h)).astype(np.float32) * 0.3
                   for _ in range(4)) \
            + (rng.standard_normal((4 * h,)).astype(np.float32) * 0.1,)
    else:
        ws = (rng.standard_normal((a * h, h)).astype(np.float32) * 0.3,
              rng.standard_normal((h,)).astype(np.float32) * 0.1)
    return (jnp.asarray(g_state), jnp.asarray(child), jnp.asarray(rows),
            jnp.asarray(cmask), tuple(jnp.asarray(w) for w in ws))


@pytest.mark.parametrize("seed", [0, 4])
@pytest.mark.parametrize("kind", ["lstm", "gru", "treelstm", "treefc"])
def test_analytic_level_bwd_matches_autodiff_oracle(kind, seed):
    """``level_megastep.level_bwd`` + ``level_param_grads`` (the math
    the fused kernel runs in VMEM) ≡ jax.vjp through the naive cell
    forward (``ref.level_bwd``) on child, pulled-row AND weight
    cotangents."""
    g_state, child, rows, cmask, ws = _level_case(kind, seed)
    g_child_a, d_gates, aux = lm.level_bwd(kind, g_state, child, rows,
                                           cmask, ws)
    w_grads_a = lm.level_param_grads(kind, d_gates, aux, ws)
    g_child_o, d_rows_o, w_grads_o = ref.level_bwd(kind, g_state, child,
                                                   rows, cmask, ws)
    np.testing.assert_allclose(np.asarray(g_child_a), np.asarray(g_child_o),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(d_gates), np.asarray(d_rows_o),
                               rtol=1e-4, atol=1e-5)
    for wa, wo in zip(w_grads_a, w_grads_o):
        np.testing.assert_allclose(np.asarray(wa), np.asarray(wo),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Fused backward kernel vs ref reverse step (one level)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["lstm", "gru", "treelstm", "treefc"])
def test_bwd_megastep_kernel_matches_ref(kind):
    """One reverse level through the Pallas kernel (interpret) ≡ the
    autodiff ref step — duplicate child rows, a sentinel child, a
    masked slot, and bit-exact preservation of every row the level does
    not touch (the in-place alias invariant)."""
    rng = np.random.default_rng(13)
    h = 5
    smult = {"lstm": 2, "treelstm": 2, "gru": 1, "treefc": 1}[kind]
    gmult = {"lstm": 4, "treelstm": 4, "gru": 3, "treefc": 1}[kind]
    a = 1 if kind in ("lstm", "gru") else 2
    S, G = smult * h, gmult * h
    T, M, t = 4, 6, 2
    buf = rng.standard_normal((T * M + 1, S)).astype(np.float32)
    buf[-1] = 0.0
    g = rng.standard_normal((T * M + 1, S)).astype(np.float32)
    cids = rng.integers(0, t * M, size=(M, a)).astype(np.int32)
    cids[0, :] = cids[1, :]                 # duplicates across slots
    cids[2, -1] = T * M                     # sentinel child
    cmask = (cids != T * M).astype(np.float32)
    eids = rng.integers(0, 10, size=(M,)).astype(np.int32)
    ext = rng.standard_normal((11, G)).astype(np.float32)
    nm = np.ones((M,), np.float32)
    nm[-1] = 0.0                            # masked slot
    _, _, _, _, ws = _level_case(kind, 13, m=M, h=h, a=a)
    out_p = lm.from_rows(lmb.bwd_megastep(
        kind, lm.as_rows(jnp.asarray(g)), lm.as_rows(jnp.asarray(buf)),
        jnp.asarray(cids), jnp.asarray(eids), jnp.asarray(nm),
        jnp.int32(t * M), lm.as_rows(jnp.asarray(ext)), ws, interpret=True))
    out_r = ref.bwd_megastep(kind, jnp.asarray(g), jnp.asarray(buf),
                             jnp.asarray(cids), jnp.asarray(cmask),
                             jnp.asarray(eids), jnp.asarray(nm), t * M,
                             jnp.asarray(ext), ws)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)
    untouched = np.setdiff1d(np.arange(T * M + 1), cids)
    np.testing.assert_array_equal(np.asarray(out_p)[untouched], g[untouched])


@pytest.mark.parametrize("kind", ["lstm", "gru", "treelstm", "treefc"])
def test_megastep_kernels_span_several_blocks(kind):
    """A level wider than one grid block (M=136 → two blocks of 68
    slots): forward and reverse kernels still match the oracles, with
    children, duplicates and the sentinel spread across blocks."""
    rng = np.random.default_rng(21)
    h, T, M, t = 4, 3, 136, 1
    smult = {"lstm": 2, "treelstm": 2, "gru": 1, "treefc": 1}[kind]
    gmult = {"lstm": 4, "treelstm": 4, "gru": 3, "treefc": 1}[kind]
    a = 1 if kind in ("lstm", "gru") else 2
    S, G = smult * h, gmult * h
    assert lm.block_rows(M) == 68
    buf = rng.standard_normal((T * M + 1, S)).astype(np.float32)
    buf[-1] = 0.0
    g = rng.standard_normal((T * M + 1, S)).astype(np.float32)
    cids = rng.integers(0, t * M, size=(M, a)).astype(np.int32)
    cids[100] = cids[3]                     # duplicates across blocks
    cids[7, -1] = cids[120, -1] = T * M     # sentinel children
    cmask = (cids != T * M).astype(np.float32)
    eids = rng.integers(0, 20, size=(M,)).astype(np.int32)
    ext = rng.standard_normal((21, G)).astype(np.float32)
    nm = (rng.random(M) > 0.1).astype(np.float32)
    _, _, _, _, ws = _level_case(kind, 21, m=M, h=h, a=a)
    rows = lambda x: lm.as_rows(jnp.asarray(x))   # noqa: E731
    fwd = lm.from_rows(lm.megastep(kind, rows(buf), jnp.asarray(cids),
                                   jnp.asarray(eids), jnp.asarray(nm),
                                   jnp.int32(t * M), rows(ext), ws,
                                   interpret=True))
    fwd_r = ref.level_megastep(kind, jnp.asarray(buf), jnp.asarray(cids),
                               jnp.asarray(cmask), jnp.asarray(eids),
                               jnp.asarray(nm), t * M, jnp.asarray(ext), ws)
    np.testing.assert_allclose(np.asarray(fwd), np.asarray(fwd_r),
                               rtol=2e-5, atol=2e-5)
    bwd = lm.from_rows(lmb.bwd_megastep(
        kind, rows(g), rows(buf), jnp.asarray(cids), jnp.asarray(eids),
        jnp.asarray(nm), jnp.int32(t * M), rows(ext), ws, interpret=True))
    bwd_r = ref.bwd_megastep(kind, jnp.asarray(g), jnp.asarray(buf),
                             jnp.asarray(cids), jnp.asarray(cmask),
                             jnp.asarray(eids), jnp.asarray(nm), t * M,
                             jnp.asarray(ext), ws)
    np.testing.assert_allclose(np.asarray(bwd), np.asarray(bwd_r),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Sorted-run scatter-add over deep buffers (the kernel holds one row in
# VMEM at a time, whatever R and n)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,d,n,block_r,block_d", [
    (40, 10, 30, 8, 512),     # 5 row panels
    (200, 130, 64, 16, 128),  # 13 panels x 2 column stripes
    (9, 6, 5, 4, 512),        # 3 panels, last one ragged
    (64, 8, 128, 8, 8),       # n >> R: every panel hit repeatedly
])
def test_scatter_add_rows_row_chunked(r, d, n, block_r, block_d):
    """Heavy duplicate accumulation and rows at former panel
    boundaries (``block_r``/``block_d`` now only shape the index
    pattern): every run must accumulate like XLA's scatter-add,
    untouched rows preserved bit-exact."""
    rng = np.random.default_rng(int(r + d + n))
    dst = rng.standard_normal((r, d)).astype(np.float32)
    idx = rng.integers(0, r, size=(n,)).astype(np.int32)
    idx[: n // 3] = idx[0]                  # heavy duplicate accumulation
    idx[-1] = r - 1                         # last row of the last panel
    idx[-2] = block_r - 1                   # last row of panel 0
    idx[-3] = block_r % r                   # first row of panel 1
    rows = rng.standard_normal((n, d)).astype(np.float32)
    out_p = lmb.scatter_add_rows(jnp.asarray(dst), jnp.asarray(idx),
                                 jnp.asarray(rows), interpret=True)
    out_r = ref.scatter_add_rows(jnp.asarray(dst), jnp.asarray(idx),
                                 jnp.asarray(rows))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)
    untouched = np.setdiff1d(np.arange(r), idx)
    np.testing.assert_array_equal(np.asarray(out_p)[untouched],
                                  dst[untouched])


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 80), st.integers(1, 60))
def test_scatter_add_rows_property(seed, r, n):
    """Any (R, n): kernel ≡ XLA scatter-add."""
    rng = np.random.default_rng(seed)
    d = int(rng.integers(1, 20))
    dst = rng.standard_normal((r, d)).astype(np.float32)
    idx = rng.integers(0, r, size=(n,)).astype(np.int32)
    rows = rng.standard_normal((n, d)).astype(np.float32)
    out_p = lmb.scatter_add_rows(jnp.asarray(dst), jnp.asarray(idx),
                                 jnp.asarray(rows), interpret=True)
    out_r = ref.scatter_add_rows(jnp.asarray(dst), jnp.asarray(idx),
                                 jnp.asarray(rows))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Structural launch census: 1 pallas launch per level, fwd AND bwd
# ---------------------------------------------------------------------------

# Promoted to a runtime surface in PR 9; the tests pin the same walker
# the profiler ships.
from repro.obs.profile import walk_jaxpr as _walk_jaxpr  # noqa: E402


@pytest.mark.parametrize("kind", ["lstm", "treelstm"])
def test_reverse_sweep_is_one_launch_per_level(kind, monkeypatch):
    """The acceptance criterion, asserted on the traced program: under
    the pallas backend the grad jaxpr contains exactly TWO scans — the
    forward megastep scan and the reverse sweep — each carrying exactly
    ONE pallas_call in its body (scan body = one level), and no
    pallas_call anywhere else (the flat lazy param pass is plain jnp).
    """
    fn, params, dev, ext = _make_case(kind, 1)

    def loss(p, e):
        buf = execute(fn, p, dev, e, fusion_mode="megastep").buf
        return jnp.sum(readout_roots(buf, dev) ** 2)

    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, ext)
    scans, outside = [], [0]
    _walk_jaxpr(jaxpr.jaxpr, scans, outside)
    assert scans == [1, 1], (
        f"expected one pallas launch per scan body (fwd megastep + rev "
        f"bwd_megastep), got per-scan counts {scans}")
    assert outside[0] == 0, (
        f"{outside[0]} pallas_call(s) outside the level scans — the flat "
        f"param pass and readouts must stay kernel-free")

    # The oracle path is kernel-free end to end.
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "chunked")
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, ext)
    scans, outside = [], [0]
    _walk_jaxpr(jaxpr.jaxpr, scans, outside)
    assert scans == [0, 0] and outside[0] == 0


# ---------------------------------------------------------------------------
# fusion_mode="megastep" error paths: messages, not just types
# ---------------------------------------------------------------------------

def _plain_vertex():
    return LambdaVertex(
        state_dim=3, ext_dim=2, arity=1,
        init_fn=lambda rng: {"w": jnp.zeros((2, 3))},
        apply_fn=lambda p, io: VertexOutput(state=io.pull() @ p["w"]),
        project_fn=lambda p, raw: raw)


def _tiny_sched(n=3, ext_dim=2, pad_arity=2):
    sched = pack_batch([chain(n)], pad_arity=pad_arity)
    ext = jnp.asarray(pack_external([np.ones((n, ext_dim), np.float32)],
                                    sched, ext_dim))
    return sched.to_device(), ext


def test_megastep_error_no_gate_spec_message():
    """A cell without a GateSpec: the error must name every failed
    requirement and echo the offending configuration."""
    fn = _plain_vertex()
    params = fn.init(jax.random.PRNGKey(0))
    dev, ext = _tiny_sched()
    with pytest.raises(
            ValueError,
            match=r"fusion_mode='megastep' needs a cell with a GateSpec "
                  r"and an eager projection, hoist=True, collect_push=False "
                  r"and a float32 buffer dtype \(got fn=LambdaVertex, "
                  r"hoist=True, collect_push=False, "):
        execute(fn, params, dev, ext, fusion_mode="megastep")


def test_megastep_error_wrong_arity_message():
    """Tree-FC packed at the wrong arity: the error must name the cell,
    both arities, and the two remedies (repack or fall back)."""
    fn = TreeFCVertex(input_dim=2, hidden=3)          # arity 2
    params = fn.init(jax.random.PRNGKey(0))
    dev, ext = _tiny_sched(pad_arity=1)               # chains pack at A=1
    with pytest.raises(
            ValueError,
            match=r"fusion_mode='megastep': TreeFCVertex declares a fixed "
                  r"gather arity 2 but the packed schedule has A=1 — repack "
                  r"with pad_arity=2 or use fusion_mode='none'"):
        execute(fn, params, dev, ext, fusion_mode="megastep")


def test_megastep_error_hoist_and_push_messages():
    """hoist=False / collect_push=True each disqualify fusion, and the
    message reports the actual flag values."""
    fn = LSTMVertex(input_dim=2, hidden=3)
    params = fn.init(jax.random.PRNGKey(0))
    dev, ext = _tiny_sched()
    with pytest.raises(ValueError, match=r"hoist=False, collect_push=False"):
        execute(fn, params, dev, ext, hoist=False, fusion_mode="megastep")
    with pytest.raises(ValueError, match=r"hoist=True, collect_push=True"):
        execute(fn, params, dev, ext, collect_push=True,
                fusion_mode="megastep")


def test_megastep_error_bad_mode_and_dtype_messages():
    fn = LSTMVertex(input_dim=2, hidden=3)
    params = fn.init(jax.random.PRNGKey(0))
    dev, ext = _tiny_sched()
    with pytest.raises(ValueError,
                       match=r"fusion_mode must be 'auto', 'megastep' or "
                             r"'none', got 'sometimes'"):
        execute(fn, params, dev, ext, fusion_mode="sometimes")
    with pytest.raises(ValueError, match=r"float32 buffer dtype"):
        execute(fn, params, dev, ext, dtype=jnp.bfloat16,
                fusion_mode="megastep")
    # Under "auto" the same configurations silently take the op-by-op
    # path instead of raising.
    assert get_gate_spec(fn) is not None
    r = execute(fn, params, dev, ext, dtype=jnp.bfloat16, fusion_mode="auto")
    assert r.buf.dtype == jnp.bfloat16
