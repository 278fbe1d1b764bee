"""Schedule-compilation pipeline (PR 4 tentpole): fingerprints, the LRU
schedule cache, shape buckets, async packing, and the acceptance
criteria — cached/bucketed/prefetched schedules produce BIT-IDENTICAL
losses and gradients vs a fresh tight ``pack_batch`` on both the fused
(pallas megastep) and unfused (op-by-op) legs, and the traced reverse
scan body contains ZERO sort ops (sorted runs are precomputed host-side
in ``pack_batch`` and carried in the schedule)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.scheduler import execute, readout_nodes, readout_roots
from repro.core.structure import (chain, pack_batch, pack_external,
                                  random_binary_tree)
from repro.models.rnn import LSTMVertex
from repro.models.treelstm import TreeLSTMVertex
from repro.pipeline import (AsyncPacker, BucketPolicy, PadDims,
                            ScheduleCache, SchedulePipeline, ShapeCensus,
                            batch_fingerprint, graph_fingerprint, tight_dims)
from repro.serve.engine import StructureRequest, StructureServeEngine

INPUT_DIM = 4


def _forest(seed, k=3, lo=2, hi=7):
    rng = np.random.default_rng(seed)
    graphs = [random_binary_tree(int(rng.integers(lo, hi)), rng)
              for _ in range(k)]
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM)).astype(np.float32)
              * 0.3 for g in graphs]
    return graphs, inputs


# ---------------------------------------------------------------------------
# Fingerprints
# ---------------------------------------------------------------------------

def test_fingerprint_deterministic_across_instances():
    rng1, rng2 = np.random.default_rng(5), np.random.default_rng(5)
    g1 = random_binary_tree(9, rng1)
    g2 = random_binary_tree(9, rng2)
    assert g1 is not g2
    assert graph_fingerprint(g1) == graph_fingerprint(g2)
    # memoized on the instance after the first call
    assert getattr(g1, "_topology_fp") == graph_fingerprint(g1)


def test_fingerprint_sensitive_to_topology_and_ext_rows():
    assert graph_fingerprint(chain(4)) != graph_fingerprint(chain(5))
    rng = np.random.default_rng(0)
    t = random_binary_tree(4, rng)
    assert graph_fingerprint(chain(7)) != graph_fingerprint(t)
    # same children, different external-row map → different schedule key
    a = chain(3)
    b = chain(3)
    b.ext_row = [2, 1, 0]
    assert graph_fingerprint(a) != graph_fingerprint(b)


def test_fingerprint_ragged_children_no_collision():
    # length-prefixing: same flat child stream, different list boundaries
    a = [[], [], [0, 1], [2]]        # node 2 gathers {0,1}; node 3 {2}
    b = [[], [], [0], [1, 2]]        # node 2 gathers {0};   node 3 {1,2}
    from repro.core.structure import InputGraph
    assert graph_fingerprint(InputGraph(children=a)) != \
        graph_fingerprint(InputGraph(children=b))


def test_batch_fingerprint_covers_order_and_pads():
    graphs, _ = _forest(1)
    assert batch_fingerprint(graphs) == batch_fingerprint(list(graphs))
    if graph_fingerprint(graphs[0]) != graph_fingerprint(graphs[1]):
        assert batch_fingerprint(graphs) != \
            batch_fingerprint(graphs[::-1])
    assert batch_fingerprint(graphs) != \
        batch_fingerprint(graphs, (8, 8, 2, 16))
    assert batch_fingerprint(graphs, (8, 8, 2, 16)) == \
        batch_fingerprint(graphs, PadDims(8, 8, 2, 16))


# ---------------------------------------------------------------------------
# ScheduleCache
# ---------------------------------------------------------------------------

def test_cache_hit_returns_equal_schedule():
    graphs, _ = _forest(2)
    cache = ScheduleCache(enabled=True)
    s1 = cache.get_or_pack(graphs)
    s2 = cache.get_or_pack(graphs)
    assert cache.hits == 1 and cache.misses == 1
    assert s1 is s2                      # by-reference reuse
    fresh = pack_batch(graphs)
    for f in ("child_ids", "child_mask", "ext_ids", "node_mask", "slot_of",
              "node_valid", "root_slots", "num_nodes", "sort_perm",
              "sorted_child_ids", "run_head"):
        np.testing.assert_array_equal(getattr(s1, f), getattr(fresh, f))


def test_cache_device_twin_cached():
    graphs, _ = _forest(3)
    cache = ScheduleCache(enabled=True)
    _, d1 = cache.get_or_pack_device(graphs)
    _, d2 = cache.get_or_pack_device(graphs)
    assert d1 is d2


def test_cache_distinguishes_pads():
    graphs, _ = _forest(4)
    cache = ScheduleCache(enabled=True)
    tight = cache.get_or_pack(graphs)
    padded = cache.get_or_pack(graphs, (tight.T + 2, tight.M + 3,
                                        tight.A, tight.N + 1))
    assert cache.misses == 2 and cache.hits == 0
    assert (padded.T, padded.M, padded.N) == \
        (tight.T + 2, tight.M + 3, tight.N + 1)


def test_cache_get_then_get_device_counts_one_lookup():
    """Regression: ``get_or_pack`` immediately followed by
    ``get_or_pack_device`` on the same key is ONE logical lookup whose
    device twin is attached after the fact — it used to be double-
    counted (a miss/hit plus a spurious second hit), inflating
    ``hit_rate``."""
    graphs, _ = _forest(2)
    cache = ScheduleCache(enabled=True, persist=False)
    s = cache.get_or_pack(graphs)                  # the logical lookup
    s2, d = cache.get_or_pack_device(graphs)       # twin attach, no count
    assert s is s2 and d is not None
    assert cache.hits == 0 and cache.misses == 1
    assert cache.hit_rate == 0.0
    # a LATER device lookup of the same key is its own logical lookup
    s3, d2 = cache.get_or_pack_device(graphs)
    assert cache.hits == 1 and cache.misses == 1
    assert d2 is d
    # an intervening lookup breaks the attach window
    cache.get_or_pack(graphs)                      # hit #2, pending
    cache.get_or_pack([chain(9)])                  # different key
    cache.get_or_pack_device(graphs)               # full lookup: hit #3
    assert cache.hits == 3 and cache.misses == 2


def test_cache_lru_eviction():
    cache = ScheduleCache(capacity=2, enabled=True)
    b1, b2, b3 = [chain(3)], [chain(4)], [chain(5)]
    cache.get_or_pack(b1)
    cache.get_or_pack(b2)
    cache.get_or_pack(b1)                # b1 most recent
    cache.get_or_pack(b3)                # evicts b2
    assert cache.evictions == 1
    cache.get_or_pack(b1)                # still resident
    assert cache.hits == 2
    cache.get_or_pack(b2)                # re-pack (was evicted)
    assert cache.misses == 4


def test_second_epoch_over_identical_corpus_hits_90_percent():
    """Cache effectiveness: a fresh cache over 24 batches of 16 trees
    serves the second epoch of the same corpus at >= 90% hits."""
    rng = np.random.default_rng(0)
    corpus = [[random_binary_tree(int(rng.integers(4, 24)), rng)
               for _ in range(16)] for _ in range(24)]
    cache = ScheduleCache(enabled=True, persist=False)
    for graphs in corpus:
        cache.get_or_pack(graphs)
    cache.reset_stats()
    for graphs in corpus:                 # the second epoch, isolated
        cache.get_or_pack(graphs)
    assert cache.hit_rate >= 0.9, cache.stats()


def test_cache_env_gate_disables():
    graphs, _ = _forest(5)
    cache = ScheduleCache(enabled=False)
    assert not cache.enabled
    s1 = cache.get_or_pack(graphs)
    s2 = cache.get_or_pack(graphs)
    assert s1 is not s2                  # every lookup cold-packs
    assert cache.hits == 0 and cache.misses == 2 and len(cache) == 0
    assert ScheduleCache().enabled       # on by default


def _counting_pack(monkeypatch):
    """Instrument cache_mod.pack_batch with a call counter."""
    import repro.pipeline.cache as cache_mod
    calls = []
    real = pack_batch

    def counted(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(cache_mod, "pack_batch", counted)
    return calls


def test_cache_disabled_pair_executes_one_pack(monkeypatch):
    """Regression: with the cache DISABLED, a ``get_or_pack`` →
    ``get_or_pack_device`` pair used to run ``pack_batch`` TWICE and
    count two misses/packs (the disabled leg returned ``key=None``, so
    the pending-attach dedupe never engaged) — 2x pack work per step.
    One logical lookup = one pack = one miss,
    enabled or not."""
    calls = _counting_pack(monkeypatch)
    graphs, _ = _forest(7)
    cache = ScheduleCache(enabled=False, persist=False)
    s = cache.get_or_pack(graphs)
    s2, d = cache.get_or_pack_device(graphs)
    assert s is s2 and d is not None
    assert len(calls) == 1
    assert cache.misses == 1 and cache.packs == 1
    # each LATER pair is its own (cold) logical lookup
    cache.get_or_pack(graphs)
    cache.get_or_pack_device(graphs)
    assert len(calls) == 2
    assert cache.misses == 2 and cache.packs == 2
    # a non-paired device lookup still cold-packs exactly once
    cache.get_or_pack_device(graphs)
    assert len(calls) == 3 and cache.packs == 3


def test_cache_pending_attach_survives_eviction(monkeypatch):
    """Regression: capacity-pressure eviction between ``get_or_pack``
    and its ``get_or_pack_device`` attach used to turn one logical
    lookup into two counted lookups (the pending key's ENTRY had been
    popped).  The pending tuple pins the entry itself, so the attach
    completes without recounting or re-packing — and re-pins the entry
    into the LRU."""
    calls = _counting_pack(monkeypatch)
    graphs, _ = _forest(8)
    cache = ScheduleCache(capacity=1, enabled=True, persist=False)
    s = cache.get_or_pack(graphs)
    # Concurrent-eviction stand-in: capacity pressure pops the entry
    # while the pair is in flight (e.g. a prefetch thread's lookups).
    cache._entries.clear()
    s2, d = cache.get_or_pack_device(graphs)
    assert s is s2 and d is not None
    assert len(calls) == 1                         # never re-packed
    assert cache.hits == 0 and cache.misses == 1
    assert len(cache) == 1                         # re-pinned
    # black-box capacity-1 flow: interleaved pairs stay one-lookup-each
    other, _ = _forest(9)
    cache.get_or_pack(other)                       # evicts `graphs`
    cache.get_or_pack_device(other)                # attach, no recount
    assert cache.misses == 2 and cache.hits == 0
    assert len(calls) == 2


def test_fingerprint_freezes_topology_mutation_raises():
    """Regression: the memoized digest went stale silently if a graph's
    ``children``/``ext_row`` were mutated after first fingerprint —
    with the graph tier that would splice a WRONG schedule under the
    stale key.  Fingerprinting freezes the topology: in-place mutation
    and rebinding both raise instead of corrupting."""
    g = chain(4)
    g.children[1].append(2)              # pre-fingerprint mutation is fine
    g.children[1].pop()
    fp = graph_fingerprint(g)
    # frozen: children/ext_row are tuples now — no in-place mutation
    with pytest.raises(AttributeError):
        g.children[1].append(2)
    with pytest.raises(TypeError):
        g.ext_row[0] = 5
    # rebinding is caught at the next fingerprint, loudly
    g.ext_row = [3, 2, 1, 0]
    with pytest.raises(ValueError, match="frozen once fingerprinted"):
        graph_fingerprint(g)
    # an untouched graph keeps returning the memoized digest
    h = chain(4)
    assert graph_fingerprint(h) == fp == graph_fingerprint(h)


# ---------------------------------------------------------------------------
# Buckets
# ---------------------------------------------------------------------------

def test_bucket_policy_quantization():
    p = BucketPolicy(round_levels=8, round_width=8, round_nodes=16)
    assert p.quantize(3, 9, 2, 17) == PadDims(8, 16, 2, 32)
    assert p.quantize(8, 8, 1, 16) == PadDims(8, 8, 1, 16)
    p2 = BucketPolicy(mode="pow2", round_levels=4, round_width=4,
                      round_nodes=8)
    assert p2.quantize(5, 9, 2, 17) == PadDims(8, 16, 2, 32)
    assert p2.quantize(1, 1, 1, 1) == PadDims(4, 4, 1, 8)


def test_bucket_policy_validation():
    with pytest.raises(ValueError, match="mode must be"):
        BucketPolicy(mode="fibonacci")
    with pytest.raises(ValueError, match="round_width"):
        BucketPolicy(round_width=0)


def test_tight_dims_matches_pack_batch():
    graphs, _ = _forest(6)
    t, m, a, n = tight_dims(graphs)
    s = pack_batch(graphs)
    assert (t, m, a, n) == (s.T, s.M, s.A, s.N)


def test_bucketed_near_miss_batches_share_shape():
    p = BucketPolicy()
    census = ShapeCensus()
    for seed in range(6):
        graphs, _ = _forest(seed, k=3, lo=2, hi=6)
        census.record(pack_batch(graphs, *p.bucket(graphs)))
    assert census.num_batches == 6
    assert census.num_shapes < 6         # bucketing collapses shapes
    tight_census = ShapeCensus()
    for seed in range(6):
        graphs, _ = _forest(seed, k=3, lo=2, hi=6)
        tight_census.record(pack_batch(graphs))
    assert census.num_shapes <= tight_census.num_shapes


# ---------------------------------------------------------------------------
# Async packing
# ---------------------------------------------------------------------------

def test_async_packer_preserves_order_and_closes():
    src = list(range(20))
    p = AsyncPacker(src, lambda x: x * x, depth=3)
    assert list(p) == [x * x for x in src]
    assert p.packed == 20
    p.close()
    assert not p._bg._thread.is_alive()


def test_async_packer_propagates_pack_errors():
    def boom(x):
        if x == 2:
            raise RuntimeError("bad batch 2")
        return x

    p = AsyncPacker([0, 1, 2, 3], boom)
    assert next(p) == 0 and next(p) == 1
    with pytest.raises(RuntimeError, match="bad batch 2"):
        next(p)
    # the end state is latched: further pulls re-raise, never hang
    with pytest.raises(RuntimeError, match="bad batch 2"):
        next(p)
    p.close()


def test_async_packer_exhaustion_is_latched():
    p = AsyncPacker([1, 2], lambda x: x)
    assert list(p) == [1, 2]
    with pytest.raises(StopIteration):
        next(p)                           # repeated next() after the end
    p.close()


def test_pipeline_prefetch_runs_cache_and_census():
    graphs, inputs = _forest(7)
    pipe = SchedulePipeline(INPUT_DIM, bucket_policy=BucketPolicy(),
                            cache=ScheduleCache(enabled=True))
    stream = pipe.prefetch(iter([(graphs, inputs)] * 4), depth=2)
    batches = list(stream)
    stream.close()
    assert len(batches) == 4
    assert pipe.cache.hits == 3 and pipe.cache.misses == 1
    assert pipe.compile_count == 1
    assert all(b.dev is batches[0].dev for b in batches)


# ---------------------------------------------------------------------------
# Parity: cached / bucketed / prefetched ≡ fresh tight pack (bit-exact)
# ---------------------------------------------------------------------------

def _loss_and_grads(fn, params, dev, ext, mode, impl, monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)

    def loss(p, e):
        buf = execute(fn, p, dev, e, fusion_mode=mode).buf
        return jnp.sum(readout_nodes(buf, dev) ** 2) \
            + jnp.sum(readout_roots(buf, dev) ** 3)

    l, g = jax.value_and_grad(loss, (0, 1))(params, ext)
    return l, g


def _assert_tree_equal(a, b):
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


def _assert_cross_pad_close(ref, got, graphs, n_ref, n_got,
                            rtol=1e-5, atol=1e-6):
    """Loss + grads across DIFFERENT pad_nodes: param grads compare
    directly; external grads live in ``[K*N + 1, X]`` matrices whose row
    maps differ, so real rows compare per sample and pad rows must be
    exactly zero (nothing pulls them)."""
    l_ref, (gp_ref, ge_ref) = ref
    l_got, (gp_got, ge_got) = got
    np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_got),
                               rtol=rtol, atol=atol)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(
        np.asarray(x), np.asarray(y), rtol=rtol, atol=atol), gp_ref, gp_got)
    K = len(graphs)
    e_ref = np.asarray(ge_ref)[:-1].reshape(K, n_ref, -1)
    e_got = np.asarray(ge_got)[:-1].reshape(K, n_got, -1)
    for k, g in enumerate(graphs):
        n = g.num_nodes
        np.testing.assert_allclose(e_ref[k, :n], e_got[k, :n],
                                   rtol=rtol, atol=atol)
        np.testing.assert_array_equal(e_got[k, n:], 0.0)


@pytest.mark.parametrize("mode,impl", [
    ("none", "chunked"),                 # unfused op-by-op leg
    ("megastep", "chunked"),             # fused VJP, jnp sweep
    ("megastep", "pallas"),              # fused VJP, one launch per level
])
def test_pipeline_parity_bit_identical(mode, impl, monkeypatch):
    """The acceptance criterion: every schedule coming out of the
    pipeline — a cache HIT, a bucketed pack, and a prefetched batch —
    yields BIT-IDENTICAL losses and gradients to a fresh ``pack_batch``
    of the same graphs at the same pads, on the unfused and both fused
    legs (the pipeline is numerically transparent: it may only skip
    work, never change it).  Bucketed-vs-TIGHT additionally agrees to
    float32 roundoff (padding changes XLA's reduction grouping by a
    few ulps; the real slots compute identical ops)."""
    graphs, inputs = _forest(11, k=3, lo=2, hi=6)
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))

    tight = pack_batch(graphs, pad_arity=2)
    ext_t = jnp.asarray(pack_external(inputs, tight, INPUT_DIM))
    ref_tight = _loss_and_grads(fn, params, tight.to_device(), ext_t, mode,
                                impl, monkeypatch)

    # -- cache hit (tight pads): bit-identical to the fresh tight pack --
    pipe_tight = SchedulePipeline(INPUT_DIM, bucket_policy=None,
                                  cache=ScheduleCache(enabled=True))
    pipe_tight.pack(graphs, inputs)      # cold
    hit = pipe_tight.pack(graphs, inputs)
    assert pipe_tight.cache.hits == 1
    got = _loss_and_grads(fn, params, hit.dev, hit.ext, mode, impl,
                          monkeypatch)
    _assert_tree_equal(ref_tight, got)

    # -- bucketed: bit-identical to a fresh pack at the SAME pads, ------
    #    roundoff-close to tight
    pipe_b = SchedulePipeline(INPUT_DIM, bucket_policy=BucketPolicy())
    bucketed = pipe_b.pack(graphs, inputs)
    assert (bucketed.sched.T, bucketed.sched.M, bucketed.sched.N) != \
        (tight.T, tight.M, tight.N)      # actually padded
    pads = pipe_b.pads_for(graphs)
    fresh_b = pack_batch(graphs, *pads)
    ext_b = jnp.asarray(pack_external(inputs, fresh_b, INPUT_DIM))
    ref_bucket = _loss_and_grads(fn, params, fresh_b.to_device(), ext_b,
                                 mode, impl, monkeypatch)
    got = _loss_and_grads(fn, params, bucketed.dev, bucketed.ext, mode,
                          impl, monkeypatch)
    _assert_tree_equal(ref_bucket, got)
    _assert_cross_pad_close(ref_tight, got, graphs, tight.N,
                            bucketed.sched.N)

    # -- prefetched: async stage must hand back the same batch ----------
    stream = pipe_tight.prefetch(iter([(graphs, inputs)]))
    pre = next(stream)
    stream.close()
    got = _loss_and_grads(fn, params, pre.dev, pre.ext, mode, impl,
                          monkeypatch)
    _assert_tree_equal(ref_tight, got)


def test_pipeline_parity_lstm_chains(monkeypatch):
    """Same criterion on the arity-1 kind (sequence LSTM over chains),
    fused pallas leg only (the other legs share the code path above)."""
    rng = np.random.default_rng(3)
    graphs = [chain(int(n)) for n in rng.integers(1, 7, size=3)]
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM)).astype(np.float32)
              for g in graphs]
    fn = LSTMVertex(input_dim=INPUT_DIM, hidden=4)
    params = fn.init(jax.random.PRNGKey(1))
    tight = pack_batch(graphs)
    ext_t = jnp.asarray(pack_external(inputs, tight, INPUT_DIM))
    ref_tight = _loss_and_grads(fn, params, tight.to_device(), ext_t,
                                "megastep", "pallas", monkeypatch)
    pipe = SchedulePipeline(INPUT_DIM, bucket_policy=BucketPolicy())
    b = pipe.pack(graphs, inputs)
    pads = pipe.pads_for(graphs)
    fresh_b = pack_batch(graphs, *pads)
    ext_b = jnp.asarray(pack_external(inputs, fresh_b, INPUT_DIM))
    ref_bucket = _loss_and_grads(fn, params, fresh_b.to_device(), ext_b,
                                 "megastep", "pallas", monkeypatch)
    got = _loss_and_grads(fn, params, b.dev, b.ext, "megastep", "pallas",
                          monkeypatch)
    _assert_tree_equal(ref_bucket, got)
    _assert_cross_pad_close(ref_tight, got, graphs, tight.N, b.sched.N)


# ---------------------------------------------------------------------------
# Sorted runs: schedule invariants + zero sorts in the reverse scan
# ---------------------------------------------------------------------------

def test_pack_batch_sorted_run_invariants():
    graphs, _ = _forest(8, k=4, lo=2, hi=9)
    s = pack_batch(graphs)
    n = s.M * s.A
    assert s.sort_perm.shape == (s.T, n)
    flat = s.child_ids.reshape(s.T, n)
    for t in range(s.T):
        perm = s.sort_perm[t]
        assert sorted(perm.tolist()) == list(range(n))     # a permutation
        np.testing.assert_array_equal(s.sorted_child_ids[t], flat[t][perm])
        np.testing.assert_array_equal(np.sort(flat[t]), s.sorted_child_ids[t])
        heads = np.ones(n, np.int32)
        heads[1:] = (s.sorted_child_ids[t][1:]
                     != s.sorted_child_ids[t][:-1]).astype(np.int32)
        np.testing.assert_array_equal(s.run_head[t], heads)


def _count_sorts(jx, in_scan_body=False, counts=None):
    """(sorts inside any scan body, sorts outside) over a jaxpr tree."""
    if counts is None:
        counts = [0, 0]
    for eqn in jx.eqns:
        if eqn.primitive.name == "sort":
            counts[0 if in_scan_body else 1] += 1
        if eqn.primitive.name == "scan":
            _count_sorts(eqn.params["jaxpr"].jaxpr, True, counts)
            continue
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None and hasattr(sub, "eqns"):
                _count_sorts(sub, in_scan_body, counts)
            elif hasattr(v, "eqns"):
                _count_sorts(v, in_scan_body, counts)
    return counts


def test_reverse_scan_body_has_zero_sort_ops(monkeypatch):
    """The acceptance criterion: with the schedule carrying precomputed
    sorted runs, the traced grad program contains NO sort anywhere —
    and stripping the runs (hand-built schedule fallback) reintroduces
    the per-level device argsort, proving the census bites."""
    graphs, inputs = _forest(9, k=3, lo=2, hi=7)
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))
    sched = pack_batch(graphs, pad_arity=2)
    dev = sched.to_device()
    ext = jnp.asarray(pack_external(inputs, sched, INPUT_DIM))
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")

    def make(dev_sched):
        def loss(p, e):
            buf = execute(fn, p, dev_sched, e, fusion_mode="megastep").buf
            return jnp.sum(readout_roots(buf, dev_sched) ** 2)
        return jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, ext)

    in_scan, outside = _count_sorts(make(dev).jaxpr)
    assert in_scan == 0, (
        f"{in_scan} sort op(s) inside the reverse scan body — sorted runs "
        f"must come precomputed from pack_batch")
    assert outside == 0, f"{outside} sort op(s) outside the scans"

    stripped = dataclasses.replace(dev, sort_perm=None,
                                   sorted_child_ids=None, run_head=None)
    in_scan, outside = _count_sorts(make(stripped).jaxpr)
    assert in_scan > 0, "negative control: fallback must sort on device"


# ---------------------------------------------------------------------------
# StructureServeEngine (the pipeline on the request path)
# ---------------------------------------------------------------------------

def test_structure_serve_engine_scores_and_caches():
    rng = np.random.default_rng(17)
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))
    eng = StructureServeEngine(
        fn, params, batch_size=3,
        pipeline=SchedulePipeline(INPUT_DIM,
                                  bucket_policy=BucketPolicy(mode="pow2"),
                                  cache=ScheduleCache(enabled=True)))
    reqs = []
    for i in range(9):
        # one topology repeated across batches → schedule-cache hits
        g = random_binary_tree(4, np.random.default_rng(0))
        x = rng.standard_normal((g.num_nodes, INPUT_DIM)).astype(np.float32)
        r = StructureRequest(i, g, x)
        reqs.append(r)
        eng.submit(r)
    done = eng.run()
    assert len(done) == 9 and eng.batches == 3
    assert eng.pipeline.cache.hits == 2       # batches 2 and 3 hit
    assert eng.pipeline.compile_count == 1
    # parity with a direct tight execute
    sched = pack_batch([r.graph for r in reqs[:3]], pad_arity=2)
    ext = jnp.asarray(pack_external(
        [r.inputs for r in reqs[:3]], sched, INPUT_DIM))
    buf = execute(fn, params, sched.to_device(), ext).buf
    roots = np.asarray(readout_roots(buf, sched.to_device()))
    for k in range(3):
        np.testing.assert_allclose(reqs[k].root_state, roots[k],
                                   rtol=1e-5, atol=1e-6)


def test_structure_serve_engine_validates_inputs():
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))
    eng = StructureServeEngine(fn, params)
    g = chain(3)
    req = StructureRequest(0, g, np.zeros((4, INPUT_DIM), np.float32))
    # Validation failures REJECT terminally (return False) instead of
    # raising: every submitted request reaches a terminal status.
    assert eng.submit(req) is False
    assert req.status == "rejected" and req.done
    assert "4 input rows for 3 nodes" in req.error
    assert req in eng.finished and not eng.queue
    assert eng.health()["rejected"] == 1


# ---------------------------------------------------------------------------
# Trainer integration: pipeline batches + auto-close
# ---------------------------------------------------------------------------

def test_trainer_consumes_async_packer_and_closes():
    from repro.train import MetricLogger, TrainConfig, Trainer

    rng = np.random.default_rng(0)
    w_true = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)

    def init_params(key):
        return {"w": jnp.zeros((8, 4), jnp.float32)}

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"]
        l = jnp.mean((pred - batch["y"]) ** 2)
        return l, {"mse": l}

    def raw():
        r = np.random.default_rng(0)
        for _ in range(40):
            x = r.standard_normal((16, 8)).astype(np.float32)
            yield {"x": x, "y": x @ np.asarray(w_true)}

    packer = AsyncPacker(raw(), lambda b: b, depth=2)
    tr = Trainer(loss_fn, init_params,
                 TrainConfig(lr=0.05, warmup_steps=5, weight_decay=0.0,
                             total_steps=30, log_every=1))
    state = tr.init_state(jax.random.PRNGKey(0))
    logger = MetricLogger(log_fn=lambda *_: None)
    state, logger = tr.fit(state, packer, steps=30, logger=logger)
    assert logger.history[-1]["loss"] < logger.history[0]["loss"]
    assert not packer._bg._thread.is_alive()   # fit closed the producer


def test_trainer_compose_reorders_with_aligned_riders():
    """``Trainer.fit(compose=...)``: epoch corpora are re-composed into
    cache-friendly batches, labels ride the reordering aligned with
    their samples, and every batch dict carries sample_ids."""
    from repro.models.treelstm import TreeLSTMVertex
    from repro.pipeline import BatchComposer
    from repro.train import MetricLogger, TrainConfig, Trainer

    rng = np.random.default_rng(0)
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    hot = random_binary_tree(4, np.random.default_rng(1))
    corpus = [hot if i % 2 == 0 else
              random_binary_tree(int(rng.integers(2, 6)), rng)
              for i in range(12)]
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) * 0.3 for g in corpus]
    # the label IS the sample id — alignment is then directly checkable
    labels = list(range(12))
    seen = []

    def loss_fn(p, batch):
        buf = execute(fn, p["cell"], batch["dev"], batch["ext"]).buf
        root_h = readout_roots(buf, batch["dev"])
        return jnp.mean(root_h ** 2), {}

    def init(key):
        return {"cell": fn.init(key)}

    class EpochSource:
        """Closable epoch stream: fit(compose=) must close the
        CALLER's source, not its internal composed-stream wrapper."""

        def __init__(self):
            self.closed = False

        def __iter__(self):
            return self

        def __next__(self):
            return corpus, inputs, {"labels": labels}

        def close(self):
            self.closed = True

    def epochs():
        return EpochSource()

    pipe = SchedulePipeline(INPUT_DIM, bucket_policy=BucketPolicy(),
                            cache=ScheduleCache(enabled=True,
                                                persist=False))
    real_pack = pipe.pack

    def spying_pack(graphs, inputs, aux=None, pads="policy"):
        # host-side interception (loss_fn only runs under trace)
        seen.append((np.asarray(aux["sample_ids"]),
                     np.asarray(aux["labels"])))
        return real_pack(graphs, inputs, aux, pads)

    pipe.pack = spying_pack
    tr = Trainer(loss_fn, init,
                 TrainConfig(lr=0.01, warmup_steps=2, weight_decay=0.0,
                             total_steps=6, log_every=1))
    state = tr.init_state(jax.random.PRNGKey(0))
    logger = MetricLogger(log_fn=lambda *_: None)
    src = epochs()
    tr.fit(state, src, steps=6, logger=logger,
           compose=BatchComposer(4, bucket_policy=pipe.bucket_policy),
           pipeline=pipe)
    assert src.closed                     # caller's source auto-closed
    assert len(seen) >= 6
    for ids, labs in seen:
        np.testing.assert_array_equal(ids, labs)   # riders stay aligned
    epoch1 = np.concatenate([ids for ids, _ in seen[:3]])
    assert sorted(epoch1.tolist()) == list(range(12))  # lossless epoch
    assert epoch1.tolist() != list(range(12))          # actually reordered
    assert pipe.cache.hits + pipe.cache.misses >= 6

    with pytest.raises(ValueError, match="compose= requires pipeline="):
        tr.fit(state, epochs(), steps=1,
               compose=BatchComposer(4))


# ---------------------------------------------------------------------------
# Lazy sorted runs: with_runs=False packing + cache upgrade coherence
# ---------------------------------------------------------------------------

def test_pack_batch_with_runs_false_omits_run_arrays():
    from repro.core.structure import attach_sorted_runs
    graphs = [random_binary_tree(5, np.random.default_rng(0)), chain(4)]
    fwd = pack_batch(graphs, with_runs=False)
    full = pack_batch(graphs, with_runs=True)
    assert fwd.sort_perm is None and fwd.sorted_child_ids is None \
        and fwd.run_head is None
    assert full.sort_perm is not None
    # attach is exactly the deferred precompute (and is idempotent)
    attached = attach_sorted_runs(fwd)
    np.testing.assert_array_equal(attached.sort_perm, full.sort_perm)
    np.testing.assert_array_equal(attached.sorted_child_ids,
                                  full.sorted_child_ids)
    np.testing.assert_array_equal(attached.run_head, full.run_head)
    assert attach_sorted_runs(attached) is attached
    # the non-run fields are unaffected by lazy packing
    np.testing.assert_array_equal(fwd.child_ids, full.child_ids)
    np.testing.assert_array_equal(fwd.node_mask, full.node_mask)


def test_cache_upgrades_runsless_entry_in_place():
    """A forward-only (serving) lookup populates the cache without run
    arrays; a later training-path lookup of the SAME key upgrades the
    entry (and rebuilds the device twin) instead of re-packing."""
    graphs = [random_binary_tree(4, np.random.default_rng(1))]
    c = ScheduleCache(enabled=True, persist=False)
    s1, d1 = c.get_or_pack_device(graphs, with_runs=False)
    assert s1.sort_perm is None and d1.sort_perm is None
    assert c.packs == 1
    s2, d2 = c.get_or_pack_device(graphs, with_runs=True)
    assert c.packs == 1 and c.hits == 1     # upgraded, not re-packed
    assert s2.sort_perm is not None and d2.sort_perm is not None
    ref = pack_batch(graphs, with_runs=True)
    np.testing.assert_array_equal(s2.sort_perm, ref.sort_perm)
    # a with_runs=False hit on the upgraded entry keeps the runs (the
    # cache never downgrades — sharing serving+training cache is sound)
    s3, _ = c.get_or_pack_device(graphs, with_runs=False)
    assert s3.sort_perm is not None


def test_disk_tier_upgrades_forward_only_entry(tmp_path):
    """A store populated by a serving pipeline (forward-only entries)
    still serves a training-path lookup: runs are attached on load, and
    the smaller entry stays on disk (no write-back)."""
    graphs = [chain(5), random_binary_tree(3, np.random.default_rng(2))]
    serve_cache = ScheduleCache(enabled=True, persist=tmp_path)
    serve_cache.get_or_pack(graphs, with_runs=False)
    size_before = serve_cache.persist.size_bytes()
    train_cache = ScheduleCache(enabled=True, persist=tmp_path)
    s = train_cache.get_or_pack(graphs, with_runs=True)
    assert train_cache.disk_hits == 1 and train_cache.packs == 0
    assert s.sort_perm is not None
    ref = pack_batch(graphs, with_runs=True)
    np.testing.assert_array_equal(s.sort_perm, ref.sort_perm)
    assert train_cache.persist.size_bytes() == size_before


def test_forward_only_entries_are_smaller(tmp_path):
    from repro.pipeline.persist import _encode
    graphs = [random_binary_tree(8, np.random.default_rng(3))
              for _ in range(4)]
    full = len(_encode(pack_batch(graphs, with_runs=True)))
    fwd = len(_encode(pack_batch(graphs, with_runs=False)))
    assert fwd < full * 0.7                # the ROADMAP hygiene win


def test_serve_engine_pipeline_packs_without_runs():
    """StructureServeEngine's default pipeline is forward-only: the
    schedules it caches carry no run arrays, and scoring still matches
    the training-path execute (existing parity tests)."""
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))
    eng = StructureServeEngine(fn, params)
    assert eng.pipeline.with_runs is False
    g = random_binary_tree(3, np.random.default_rng(4))
    x = np.random.default_rng(4).standard_normal(
        (g.num_nodes, INPUT_DIM)).astype(np.float32)
    req = StructureRequest(0, g, x)
    eng.submit(req)
    eng.run()
    assert req.status == "ok"
    sched = eng.pipeline.cache.get_or_pack([g], eng.pipeline.pads_for([g]),
                                           with_runs=False)
    assert sched.sort_perm is None


# ---------------------------------------------------------------------------
# ShardedPipeline (data-parallel stacking, per-replica caches)
# ---------------------------------------------------------------------------

def test_sharded_pipeline_pack_step_stacks_replicas():
    from repro.pipeline import ShardedPipeline

    rng = np.random.default_rng(0)
    graphs = [random_binary_tree(int(rng.integers(2, 10)), rng)
              for _ in range(32)]
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) for g in graphs]
    labels = np.arange(32)
    sp = ShardedPipeline(INPUT_DIM, 4)
    comp = sp.composer(8)
    steps, _ = comp.compose_sharded(graphs, inputs, {"label": labels},
                                    num_shards=4)
    b = sp.pack_step(steps[0])
    k = len(steps[0].replicas[0].graphs)
    pads = steps[0].pads
    assert b["ext"].shape == (4, pads.nodes * k + 1, INPUT_DIM)
    assert b["weights"].shape == (4, k)
    assert b["sample_ids"].shape == (4, k)
    assert b["label"].shape == (4, k)
    for leaf in jax.tree.leaves(b["dev"]):
        assert leaf.shape[0] == 4
    # stacked leaves equal each replica's solo pack (same pads)
    solo = SchedulePipeline(INPUT_DIM)
    for r, rep in enumerate(steps[0].replicas):
        pb = solo.pack(rep.graphs, rep.inputs, pads=pads)
        jax.tree.map(lambda s, d: np.testing.assert_array_equal(
            np.asarray(s), np.asarray(d[r])), pb.dev, b["dev"])
        np.testing.assert_array_equal(np.asarray(pb.ext),
                                      np.asarray(b["ext"][r]))


def test_sharded_pipeline_epoch2_hit_rate_matches_unsharded():
    """Acceptance criterion (d): in epoch 2 every replica's measured
    cache hit rate equals the unsharded composer's — the stable
    per-replica fingerprint streams land every lookup in that replica's
    warm cache."""
    from repro.pipeline import ShardedPipeline

    rng = np.random.default_rng(5)
    graphs = [random_binary_tree(int(rng.integers(2, 12)), rng)
              for _ in range(64)]
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) for g in graphs]

    # unsharded reference: composed epochs through one pipeline
    up = SchedulePipeline(INPUT_DIM)
    ucomp = up.composer(16)
    for _ in range(2):
        snap = dict(up.cache.stats())
        for cb in ucomp.compose(graphs, inputs)[0]:
            up.pack(*cb.as_item())
    u = up.cache.stats()
    u_lookups = (u["hits"] - snap["hits"]) + (u["misses"] - snap["misses"])
    u_rate = (u["hits"] - snap["hits"]) / u_lookups
    assert u_rate == 1.0                        # epoch 2 fully warm

    sp = ShardedPipeline(INPUT_DIM, 4)
    scomp = sp.composer(16)
    for _ in range(2):
        snaps = [dict(p.cache.stats()) for p in sp.pipes]
        for st in scomp.compose_sharded(graphs, inputs, num_shards=4)[0]:
            sp.pack_step(st)
    for r, p in enumerate(sp.pipes):
        s = p.cache.stats()
        d_hits = s["hits"] - snaps[r]["hits"]
        d_miss = s["misses"] - snaps[r]["misses"]
        assert d_hits + d_miss > 0
        assert d_hits / (d_hits + d_miss) == u_rate, (r, d_hits, d_miss)


def test_sharded_epoch2_balance_and_hits_on_eight_replicas():
    """256 trees of 2-31 nodes, steps of 32 over 8 replicas: replica
    node imbalance <= 1.15, and every replica's second-epoch cache hit
    rate >= 0.99."""
    from repro.pipeline import ShardedPipeline

    rng = np.random.default_rng(0)
    graphs = [random_binary_tree(int(rng.integers(2, 32)), rng)
              for _ in range(256)]
    inputs = [rng.standard_normal((g.num_nodes, 8)).astype(np.float32)
              * 0.3 for g in graphs]
    sp = ShardedPipeline(8, 8)
    comp = sp.composer(32)
    steps, stats = comp.compose_sharded(graphs, inputs, num_shards=8)
    assert stats.node_imbalance <= 1.15, stats.replica_nodes
    for st in steps:
        sp.pack_step(st)
    snaps = [dict(p.cache.stats()) for p in sp.pipes]
    for st in comp.compose_sharded(graphs, inputs, num_shards=8)[0]:
        sp.pack_step(st)
    for r, p in enumerate(sp.pipes):
        s = p.cache.stats()
        hits = s["hits"] - snaps[r]["hits"]
        total = hits + s["misses"] - snaps[r]["misses"]
        assert total and hits / total >= 0.99, (r, hits, total)


def test_sharded_pipeline_validates():
    from repro.pipeline import ShardedPipeline, ShardedStep

    with pytest.raises(ValueError, match="num_shards"):
        ShardedPipeline(INPUT_DIM, 0)
    sp = ShardedPipeline(INPUT_DIM, 2)
    comp = sp.composer(8)
    steps, _ = comp.compose_sharded([chain(2)] * 8, num_shards=2)
    bad = ShardedStep(replicas=steps[0].replicas[:1], pads=steps[0].pads)
    with pytest.raises(ValueError, match="replicas"):
        sp.pack_step(bad)
