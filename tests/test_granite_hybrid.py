"""Granite-4.0-H through the continuous engine, at a small size, against
the plain reference (``bench/reference/granite_hybrid.py``) on seeded
random weights; the two-pool cache manager's bookkeeping; the Mamba-2
one-token kernel against ``ref.ssd_decode_step``.

Tolerance: the engine runs each token through the period once, with
its state carried in the arena; the reference runs the whole sequence
at once.  Both are float32 at ``highest``, but the sums run in
different orders (the SSM output's sum over the state dimension, the
attention's softmax over paged blocks, matrix products at other
shapes), so logits agree to rounding, not bit for bit (ROADMAP C1).
Over ten layers the gap measured here is 2.5e-7 to 2.9e-7 of the
logits' RMS; ``RTOL`` allows ten times that, and the reference with
three-pass bfloat16 products (``high``) is 1.5e-5 to 1.7e-5 away, five
times past it.  A lost term (a state hand-off) moves the logits by far
more.
"""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.structure import InputGraph
from repro.kernels import ref, ssm_step as ks
from repro.models.granite_hybrid import GraniteHybridVertex, HybridDims
from repro.serve import ContinuousRequest
from repro.serve.continuous import AdmissionPolicy, HybridChainEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RTOL = 3e-6

CFG = {"hidden_size": 64, "vocab_size": 512, "num_hidden_layers": 10,
       "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
       "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 8,
       "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "attention_multiplier": 0.015625, "shared_intermediate_size": 128,
       "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
       "residual_multiplier": 0.22, "logits_scaling": 8,
       "num_local_experts": 0}


def _to_tiles(state, W):
    """``[M, H, P, N]`` → the kernel's ``[M, R, N, W]`` tiles."""
    M, H, P, N = state.shape
    return state.reshape(M, H * P // W, W, N).transpose(0, 1, 3, 2)


def _from_tiles(tiles, H):
    M, R, N, W = tiles.shape
    return tiles.transpose(0, 1, 3, 2).reshape(M, H, R * W // H, N)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "reference_granite_hybrid",
        os.path.join(ROOT, "bench", "reference", "granite_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    jax.config.update("jax_default_matmul_precision", "highest")
    refmod = _reference()
    params = refmod.init(jax.random.PRNGKey(7), CFG)
    fn = GraniteHybridVertex(HybridDims.from_hf(CFG), kv_block=8, max_len=48)
    return fn, params, refmod


def _request(rid, tokens):
    n = len(tokens)
    return ContinuousRequest(
        request_id=rid,
        graph=InputGraph(children=[[] if t == 0 else [t - 1]
                                   for t in range(n)]),
        inputs=np.asarray(tokens, np.float32)[:, None])


def _ref_logits(refmod, params, seqs):
    L = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), L), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    last = np.array([len(s) - 1 for s in seqs])
    with jax.default_matmul_precision("highest"):
        return np.asarray(refmod.last_logits(params, CFG, jnp.asarray(toks),
                                             jnp.asarray(last), q_block=16))


def _engine(fn, params, **kw):
    kw.setdefault("num_rows", 8)
    kw.setdefault("kv_tokens", 8 * 48)
    kw.setdefault("frontier_width", 4)
    return HybridChainEngine(fn, params, clock=lambda: 0.0,
                             policy=AdmissionPolicy(min_occupancy=0.0,
                                                    max_window=4), **kw)


def _gap(a, b):
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))


@pytest.mark.parametrize("schedule", ["together", "staggered", "one_lane"])
def test_engine_matches_reference(model, schedule):
    fn, params, refmod = model
    rng = np.random.default_rng({"together": 1, "staggered": 2,
                                 "one_lane": 3}[schedule])
    lens = [5, 17, 9, 30, 1, 12]
    seqs = [rng.integers(0, CFG["vocab_size"], n) for n in lens]
    eng = _engine(fn, params,
                  frontier_width=1 if schedule == "one_lane" else 4)
    reqs = [_request(i, s) for i, s in enumerate(seqs)]
    if schedule == "staggered":
        for r in reqs:                 # each arrives a few ticks apart
            eng.submit(r)
            for _ in range(3):
                eng.step()
    else:
        for r in reqs:
            eng.submit(r)
    eng.run()
    assert [r.status for r in reqs] == ["ok"] * len(reqs)
    want = _ref_logits(refmod, params, seqs)
    for r, w in zip(reqs, want):
        assert r.logits.shape == (CFG["vocab_size"],)
        assert _gap(r.logits, w) < RTOL, (r.request_id, _gap(r.logits, w))


def test_a_lost_state_handoff_is_caught(model):
    """The tolerance is tight: dropping the state a token hands on
    (every vertex its chain's first) moves the logits far past it."""
    fn, params, refmod = model
    seq = np.arange(3, 23)
    want = _ref_logits(refmod, params, [seq])[0]
    eng = _engine(fn, params)
    req = _request(0, seq)
    eng.submit(req)
    real = eng._stack_window

    def forget(ticks):
        args = real(ticks)
        io = args[2]
        io = type(io)(**{**io.__dict__,
                         "flags": jnp.minimum(io.flags, 1)})
        return args[:2] + (io,) + args[3:]

    eng._stack_window = forget
    eng.run()
    assert req.status == "ok"
    assert _gap(req.logits, want) > 100 * RTOL


def test_state_row_held_in_place_and_reused(model):
    """A chain keeps one state row from its first vertex to its last,
    no two live chains share one, and a retired chain's row goes to a
    later chain (whose first vertex does not read what it left)."""
    fn, params, refmod = model
    eng = _engine(fn, params, num_rows=2, frontier_width=2)
    seen = []
    real = eng._stack_window

    def spy(ticks):
        for lanes in ticks:
            rows = [a.row for a, _t in lanes]
            assert len(set(rows)) == len(rows)
            seen.extend((a.req.request_id, a.row) for a, _t in lanes)
        return real(ticks)

    eng._stack_window = spy
    seqs = [np.arange(1, 7), np.arange(2, 12), np.arange(5, 9)]
    reqs = [_request(i, s) for i, s in enumerate(seqs)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.status == "ok" for r in reqs)
    rows = {}
    for rid, row in seen:
        rows.setdefault(rid, set()).add(row)
    assert all(len(v) == 1 for v in rows.values())
    # the third request ran in the row the first one freed at retirement
    assert rows[2] == rows[0]
    assert eng.free_rows == 2 and eng.free_kv_blocks == eng.num_kv_blocks
    want = _ref_logits(refmod, params, seqs)
    for r, w in zip(reqs, want):
        assert _gap(r.logits, w) < RTOL, (r.request_id, _gap(r.logits, w))


def test_kv_blocks_held_for_the_request_life(model):
    fn, params, _ = model
    eng = _engine(fn, params, kv_tokens=8 * 12)
    reqs = [_request(0, np.arange(1, 30)), _request(1, np.arange(1, 10))]
    for r in reqs:
        eng.submit(r)
    held = {}
    while eng.step():
        for a in eng._active:
            held.setdefault(a.req.request_id, set()).update(a.blocks)
            assert not set(a.blocks) & set(eng._kv_free)
            assert len(a.blocks) == -(-a.length // 8)
    assert held[0].isdisjoint(held[1])
    assert eng.free_kv_blocks == eng.num_kv_blocks


@pytest.mark.parametrize("budget", ["state_rows", "kv_blocks"])
def test_admission_blocked_by_each_budget(model, budget):
    fn, params, _ = model
    if budget == "state_rows":         # two chains' rows, plenty of K/V
        eng = _engine(fn, params, num_rows=2, kv_tokens=8 * 48)
    else:                              # K/V for one 40-token chain only
        eng = _engine(fn, params, num_rows=8, kv_tokens=8 * 5)
    lens = [40, 3, 3] if budget == "kv_blocks" else [6, 6, 6]
    reqs = [_request(i, np.arange(1, n + 1)) for i, n in enumerate(lens)]
    for r in reqs:
        eng.submit(r)
    eng._admit()
    assert eng.num_active == (1 if budget == "kv_blocks" else 2)
    assert len(eng.queue) == len(reqs) - eng.num_active
    eng.run()
    assert all(r.status == "ok" for r in reqs)


def test_submit_rejects_non_chains_and_overlong(model):
    fn, params, _ = model
    eng = _engine(fn, params)
    tree = ContinuousRequest(request_id=0,
                             graph=InputGraph(children=[[], [], [0, 1]]),
                             inputs=np.zeros((3, 1), np.float32))
    assert not eng.submit(tree)
    assert not eng.submit(_request(1, np.arange(60)))
    assert [r.status for r in eng.finished] == ["rejected", "rejected"]


@pytest.mark.parametrize("impl", ["pallas", "chunked"])
def test_ssm_step_matches_decode_step(impl):
    """The kernel (interpret mode) and its op-by-op twin against the
    one-token conv and ``ref.ssd_decode_step``, each lane updating its
    row in place: lanes with a predecessor, a first token (zero state
    and tail, the stale row unread) and a pad lane."""
    from repro.kernels import ops as kops
    M, H, P, N, W, K, L = 5, 4, 16, 8, 32, 4, 3
    R, Din = H * P // W, H * P
    C, CR, step = Din + 2 * N, R + 2, ks.slot_rows(R + 2)
    rng = np.random.default_rng(0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    rows = lambda v: ks.to_conv_rows(f32(v), Din, N, W)
    arena = f32(rng.standard_normal((9, L, R * N + (K - 1) * step, W)))
    arena = arena.at[8].set(jnp.nan)          # spare row: never read
    state = f32(rng.standard_normal((M, H, P, N)))
    tail = f32(rng.standard_normal((M, K - 1, C)))
    lane_rows, flags = np.array([0, 2, 5, 4, 8]), np.array([2, 2, 1, 2, 0])
    arena = arena.at[5].set(jnp.nan)          # a retired chain's row
    tiles = _to_tiles(state, W).reshape(M, R * N, W)
    for i in np.nonzero(flags == 2)[0]:
        arena = arena.at[lane_rows[i], 1, :R * N].set(tiles[i])
        for k in range(K - 1):
            o = R * N + k * step
            arena = arena.at[lane_rows[i], 1, o:o + CR].set(rows(tail[i, k]))
    new = f32(rng.standard_normal((M, C)))
    w, b = f32(rng.uniform(-.5, .5, (K, C))), f32(rng.uniform(-.5, .5, C))
    dt = f32(rng.uniform(0.01, 0.2, (M, H)))
    A = -jnp.exp(f32(rng.standard_normal(H)))
    D = f32(rng.standard_normal(H))
    y, out = kops.ssm_step(
        arena, 1, jnp.asarray(lane_rows), jnp.asarray(flags),
        rows(new), jnp.repeat(dt, P, axis=1).reshape(M, R, W), rows(w),
        rows(b), jnp.repeat(A, P).reshape(R, W),
        jnp.repeat(D, P).reshape(R, W), impl=impl)
    has = (flags == 2)[:, None, None]
    window = jnp.concatenate([jnp.where(has, tail, 0.0), new[:, None]], 1)
    xbc = jax.nn.silu(jnp.sum(window * w[None], axis=1) + b)
    s0 = jnp.where(has[..., None], state, 0.0)
    y_ref, s_ref = ref.ssd_decode_step(
        xbc[:, :Din].reshape(M, H, P), dt, A, xbc[:, Din:Din + N],
        xbc[:, Din + N:], D, s0)
    live = flags > 0
    np.testing.assert_allclose(np.asarray(y.reshape(M, H, P))[live],
                               np.asarray(y_ref)[live], rtol=1e-5, atol=1e-5)
    got = _from_tiles(out[lane_rows, 1, :R * N].reshape(M, R, N, W), H)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(s_ref)[live],
                               rtol=1e-5, atol=1e-5)
    for k in range(K - 1):                    # the tail moved up one slot
        o = R * N + k * step
        np.testing.assert_allclose(
            np.asarray(out[lane_rows, 1, o:o + CR])[live],
            np.asarray(rows(window[:, k + 1]))[live], rtol=0, atol=0)
    # every block the lanes did not write is as it was
    np.testing.assert_array_equal(np.asarray(out[:8, [0, 2]]),
                                  np.asarray(arena[:8, [0, 2]]))
    np.testing.assert_array_equal(np.asarray(out[[1, 3, 6, 7], 1]),
                                  np.asarray(arena[[1, 3, 6, 7], 1]))
