"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) ≡ ref.py
oracle.  Each kernel gets odd/aligned shapes and both dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (cell_kernels, decode_attention as dec,
                           flash_attention as fa, gather_scatter as gsc,
                           level_megastep as lm, mamba_ssd, ref)

KEY = jax.random.PRNGKey(0)


def keys(n):
    return list(jax.random.split(KEY, n))


# ---------------------------------------------------------------------------
# Fused cells
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,h", [(1, 8), (37, 50), (128, 128), (200, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_gates(m, h, dtype):
    k1, k2 = keys(2)
    g = jax.random.normal(k1, (m, 4 * h), dtype)
    c = jax.random.normal(k2, (m, h), dtype)
    c1, h1 = cell_kernels.lstm_gates(g, c, interpret=True)
    c2, h2 = ref.lstm_gates(g, c)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(c1, np.float32),
                               np.asarray(c2, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("m,a,h", [(5, 2, 16), (64, 3, 40), (130, 2, 128)])
def test_treelstm_gates(m, a, h):
    k1, k2, k3, k4, k5 = keys(5)
    i = jax.random.normal(k1, (m, h))
    f = jax.random.normal(k2, (m, a, h))
    o = jax.random.normal(k3, (m, h))
    u = jax.random.normal(k4, (m, h))
    ck = jax.random.normal(k5, (m, a, h))
    mask = (jax.random.uniform(k1, (m, a)) > 0.3).astype(jnp.float32)
    c1, h1 = cell_kernels.treelstm_gates(i, f, o, u, ck, mask, interpret=True)
    c2, h2 = ref.treelstm_gates(i, f, o, u, ck, mask)
    np.testing.assert_allclose(c1, c2, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h1, h2, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Gather / scatter (the Cavs primitives' kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,d,n", [(10, 8, 4), (100, 130, 33), (64, 512, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_rows(r, d, n, dtype):
    k1, k2 = keys(2)
    src = jax.random.normal(k1, (r, d), dtype)
    idx = jax.random.randint(k2, (n,), 0, r, jnp.int32)
    out = lm.from_rows(gsc.gather_rows(lm.as_rows(src), idx, interpret=True))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.gather_rows(src, idx)))


@pytest.mark.parametrize("r,d,n", [(10, 8, 4), (100, 130, 30)])
def test_scatter_rows(r, d, n):
    k1, k2 = keys(2)
    dst = jax.random.normal(k1, (r, d))
    rows = jax.random.normal(k2, (n, d))
    idx = jnp.asarray(np.random.default_rng(0).choice(r, n, replace=False),
                      jnp.int32)
    out = lm.from_rows(gsc.scatter_rows(lm.as_rows(dst), idx,
                                        lm.as_rows(rows), interpret=True))
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.scatter_rows(dst, idx, rows)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("sq,sk", [(64, 64), (40, 72)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(hq, hkv, sq, sk, causal):
    k1, k2, k3 = keys(3)
    q = jax.random.normal(k1, (2, hq, sq, 32))
    k = jax.random.normal(k2, (2, hkv, sk, 32))
    v = jax.random.normal(k3, (2, hkv, sk, 32))
    o1 = fa.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                            interpret=True)
    o2 = ref.mha(q, k, v, causal=causal)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


def test_flash_attention_window():
    k1, k2, k3 = keys(3)
    q = jax.random.normal(k1, (1, 2, 96, 16))
    k = jax.random.normal(k2, (1, 2, 96, 16))
    v = jax.random.normal(k3, (1, 2, 96, 16))
    o1 = fa.flash_attention(q, k, v, causal=True, window=24, block_q=32,
                            block_k=32, interpret=True)
    o2 = ref.mha(q, k, v, causal=True, window=24)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_twin_matches_ref(dtype):
    """The CPU-lowering twin must implement the same math as the kernel."""
    k1, k2, k3 = keys(3)
    q = jax.random.normal(k1, (2, 4, 70, 24), dtype)
    k = jax.random.normal(k2, (2, 2, 70, 24), dtype)
    v = jax.random.normal(k3, (2, 2, 70, 24), dtype)
    o1 = fa.attention_chunked(q, k, v, causal=True, block_q=32, block_k=32)
    o2 = ref.mha(q, k, v, causal=True)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# Decode attention (ragged kv_len + window)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv,s", [(4, 4, 33), (8, 2, 128)])
def test_decode_attention(hq, hkv, s):
    k1, k2, k3 = keys(3)
    q = jax.random.normal(k1, (3, hq, 32))
    k = jax.random.normal(k2, (3, hkv, s, 32))
    v = jax.random.normal(k3, (3, hkv, s, 32))
    kvl = jnp.asarray([s, max(1, s // 2), 1], jnp.int32)
    o1 = dec.decode_attention(q, k, v, kv_len=kvl, block_k=32,
                              interpret=True)
    o2 = ref.decode_attention(q, k, v, kv_len=kvl)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)
    o3 = dec.decode_attention_chunked(q, k, v, kv_len=kvl, block_k=32)
    np.testing.assert_allclose(o3, o2, rtol=2e-5, atol=2e-5)


def test_decode_attention_window():
    k1, k2, k3 = keys(3)
    q = jax.random.normal(k1, (2, 4, 16))
    k = jax.random.normal(k2, (2, 4, 64, 16))
    v = jax.random.normal(k3, (2, 4, 64, 16))
    kvl = jnp.asarray([64, 40], jnp.int32)
    o1 = dec.decode_attention(q, k, v, kv_len=kvl, window=16, block_k=16,
                              interpret=True)
    o2 = ref.decode_attention(q, k, v, kv_len=kvl, window=16)
    np.testing.assert_allclose(o1, o2, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Mamba-2 SSD
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("l,chunk", [(32, 8), (64, 16), (48, 16)])
def test_ssd_chunk_scan(l, chunk):
    B, H, P, N = 2, 3, 8, 4
    k1, k2, k3, k4, k5 = keys(5)
    x = jax.random.normal(k1, (B, l, H, P))
    dt = jax.nn.softplus(jax.random.normal(k2, (B, l, H)))
    A = -jnp.exp(jax.random.normal(k3, (H,)) * 0.3)
    Bm = jax.random.normal(k4, (B, l, N))
    Cm = jax.random.normal(k5, (B, l, N))
    D = jnp.ones((H,))
    if l % chunk:
        pytest.skip("kernel requires chunk | length (ops.py pads)")
    y1, s1 = mamba_ssd.ssd_chunk_scan(x, dt, A, Bm, Cm, D, chunk=chunk,
                                      interpret=True)
    y2, s2 = ref.ssd_reference(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)


def test_ssd_initial_state_and_decode_chain():
    """Chunked prefill state + serial decode steps ≡ one long reference."""
    B, L, H, P, N = 1, 24, 2, 4, 3
    k1, k2, k3, k4, k5 = keys(5)
    x = jax.random.normal(k1, (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k2, (B, L, H)))
    A = -jnp.exp(jax.random.normal(k3, (H,)) * 0.3)
    Bm = jax.random.normal(k4, (B, L, N))
    Cm = jax.random.normal(k5, (B, L, N))
    D = jnp.ones((H,))
    y_all, s_all = ref.ssd_reference(x, dt, A, Bm, Cm, D)

    cut = 16
    y1, s1 = mamba_ssd.ssd_chunk_scan(x[:, :cut], dt[:, :cut], A,
                                      Bm[:, :cut], Cm[:, :cut], D, chunk=8,
                                      interpret=True)
    state = s1
    ys = []
    for t in range(cut, L):
        y_t, state = ref.ssd_decode_step(
            x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], D, state)
        ys.append(y_t)
    np.testing.assert_allclose(y1, y_all[:, :cut], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(jnp.stack(ys, 1),
                               y_all[:, cut:], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(state, s_all, rtol=1e-4, atol=1e-4)


def test_ops_dispatch_pads_ragged_seq():
    """ops.ssd pads non-multiple lengths and still matches the oracle."""
    from repro.kernels import ops as kops
    B, L, H, P, N = 1, 21, 2, 4, 3
    k1, k2, k3, k4, k5 = keys(5)
    x = jax.random.normal(k1, (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(k2, (B, L, H)))
    A = -jnp.exp(jax.random.normal(k3, (H,)) * 0.3)
    Bm = jax.random.normal(k4, (B, L, N))
    Cm = jax.random.normal(k5, (B, L, N))
    D = jnp.ones((H,))
    y1, s1 = kops.ssd(x, dt, A, Bm, Cm, D, chunk=8, impl="chunked")
    y2, s2 = ref.ssd_reference(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Fused level step (recurrent matmul + cell in one kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,h", [(3, 16), (64, 64), (130, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lstm_level_fused(m, h, dtype):
    from repro.kernels import level_step
    k1, k2, k3, k4, k5 = keys(5)
    hp = jax.random.normal(k1, (m, h), dtype)
    cp = jax.random.normal(k2, (m, h), dtype)
    ext = jax.random.normal(k3, (m, 4 * h), dtype)
    wh = jax.random.normal(k4, (h, 4 * h), dtype) * 0.2
    b = jax.random.normal(k5, (4 * h,), dtype)
    c1, h1 = level_step.lstm_level_fused(hp, cp, ext, wh, b, block_m=32,
                                         interpret=True)
    c2, h2 = ref.lstm_level_fused(hp, cp, ext, wh, b)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(c1, np.float32),
                               np.asarray(c2, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), rtol=tol, atol=tol)


def test_fused_vertex_matches_jnp_cell():
    """LSTMVertex(cell_impl='fused') ≡ the jnp cell through the full
    scheduler (interpret-mode Pallas on CPU)."""
    from repro.core.scheduler import execute
    from repro.core.structure import chain, pack_batch, pack_external
    from repro.models.rnn import LSTMVertex

    fn_ref = LSTMVertex(input_dim=6, hidden=16)
    fn_fused = LSTMVertex(input_dim=6, hidden=16, cell_impl="fused")
    params = fn_ref.init(jax.random.PRNGKey(0))
    graphs = [chain(5), chain(3)]
    rng = np.random.default_rng(0)
    inputs = [rng.standard_normal((g.num_nodes, 6)).astype(np.float32)
              for g in graphs]
    sched = pack_batch(graphs)
    ext = jnp.asarray(pack_external(inputs, sched, 6))
    dev = sched.to_device()
    r1 = execute(fn_ref, params, dev, ext)
    r2 = execute(fn_fused, params, dev, ext)
    np.testing.assert_allclose(np.asarray(r1.buf), np.asarray(r2.buf),
                               rtol=2e-5, atol=2e-5)
