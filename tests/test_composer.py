"""Pipeline-aware batch composition (PR 5 tentpole).

Composition REORDERS samples to manufacture schedule-cache hits, so
these tests carry the correctness burden: property tests prove the
composer is a LOSSLESS PERMUTATION (no drop, no duplicate, aux riders
aligned, every batch within its bucket's pad bounds), and the
end-to-end test proves ORDER INDEPENDENCE — per-sample losses and
per-sample gradients from a composed epoch are bit-identical (after
realignment by sample id) to a FIFO epoch on the unfused, fused-chunked
and fused-pallas legs.  (Epoch-summed PARAMETER grads are compared to
float32 roundoff instead: composition permutes slot assignment, and
the flat per-slot grad reduction is order-sensitive in fp arithmetic —
per-sample quantities have no such cross-sample reduction.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.scheduler import execute, readout_roots
from repro.core.structure import (InputGraph, chain, pack_batch,
                                  pack_external, random_binary_tree)
from repro.data import ComposedBatchSource
from repro.models.treelstm import TreeLSTMVertex
from repro.pipeline import (BatchComposer, BucketPolicy, PadDims,
                            ScheduleCache, SchedulePipeline, fifo_stats,
                            tight_dims)
from repro.serve.engine import StructureRequest, StructureServeEngine

from tests.hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

INPUT_DIM = 4


def _random_corpus(rng: np.random.Generator, n: int, dup_frac: float = 0.5):
    """Mixed chains/trees with duplicated topologies: ``dup_frac`` of
    samples reuse one of a few hot shapes (identity-distinct objects,
    equal fingerprints)."""
    hot = [chain(5), random_binary_tree(4, np.random.default_rng(1)),
           chain(2)]
    corpus = []
    for _ in range(n):
        r = rng.random()
        if r < dup_frac:
            src = hot[int(rng.integers(len(hot)))]
            corpus.append(InputGraph(children=[list(c)
                                               for c in src.children]))
        elif r < dup_frac + 0.25:
            corpus.append(chain(int(rng.integers(1, 9))))
        else:
            corpus.append(random_binary_tree(int(rng.integers(2, 9)), rng))
    return corpus


# ---------------------------------------------------------------------------
# Sharded composition (data-parallel replica splitting)
# ---------------------------------------------------------------------------

def test_compose_sharded_is_lossless_and_equal_cardinality():
    rng = np.random.default_rng(0)
    corpus = _random_corpus(rng, 83)           # ragged tail on purpose
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) for g in corpus]
    labels = list(range(len(corpus)))
    comp = BatchComposer(16)
    steps, stats = comp.compose_sharded(corpus, inputs, {"label": labels},
                                        num_shards=4)
    # every real sample exactly once; fillers are weight-0 / id -1
    ids = np.concatenate([r.sample_ids for st in steps
                          for r in st.replicas])
    assert np.array_equal(np.sort(ids[ids >= 0]), np.arange(len(corpus)))
    for st in steps:
        assert len({len(r.graphs) for r in st.replicas}) == 1
        assert all(r.pads == st.pads for r in st.replicas)
        for rep in st.replicas:
            for sid, w, lab, g, x in zip(
                    rep.sample_ids, rep.aux["weights"],
                    rep.aux["label"], rep.graphs, rep.inputs):
                if sid >= 0:
                    assert w == 1.0 and lab == sid
                    assert g is corpus[sid] and x is inputs[sid]
                else:
                    assert w == 0.0
            # replica fits the step's pad cover
            t, m, a, n = tight_dims(rep.graphs)
            assert (t <= st.pads.levels and m <= st.pads.width
                    and a <= st.pads.arity and n <= st.pads.nodes)
    assert stats.num_fillers == sum(
        int(np.sum(r.sample_ids < 0)) for st in steps for r in st.replicas)


def test_compose_sharded_balances_node_counts():
    """The acceptance bar: ≤1.15x max/min total node count across
    replicas on a realistic mixed corpus."""
    rng = np.random.default_rng(7)
    corpus = [random_binary_tree(int(rng.integers(2, 40)), rng)
              for _ in range(256)]
    comp = BatchComposer(32)
    _, stats = comp.compose_sharded(corpus, num_shards=8)
    assert stats.node_imbalance <= 1.15, stats.replica_nodes


def test_compose_sharded_fingerprints_stable_across_epochs():
    """Replica r's batch-fingerprint stream must be identical epoch
    over epoch (that is what keeps every replica's schedule cache hot)
    — including under a corpus shuffle, because the split keys on
    topology digests, not arrival order."""
    from repro.pipeline import batch_fingerprint

    rng = np.random.default_rng(3)
    corpus = _random_corpus(rng, 96)
    comp = BatchComposer(16)

    def fp_streams(graphs):
        steps, _ = comp.compose_sharded(graphs, num_shards=4)
        return [[batch_fingerprint(st.replicas[r].graphs, st.pads)
                 for st in steps] for r in range(4)]

    a = fp_streams(corpus)
    b = fp_streams(corpus)                     # same epoch again
    assert a == b
    perm = rng.permutation(len(corpus))
    shuffled = [corpus[i] for i in perm]
    c = fp_streams(shuffled)
    assert a == c                              # order-independent


def test_compose_sharded_matches_unsharded_plan_and_hit_rate():
    """Sharding must not change WHAT is in each step: step t's union of
    real samples equals unsharded batch t, and the predicted
    per-replica hit rate is no worse than the unsharded one (grouped
    batches stay grouped after splitting)."""
    rng = np.random.default_rng(11)
    corpus = _random_corpus(rng, 128, dup_frac=0.6)
    comp = BatchComposer(16)
    batches, ustats = comp.compose(corpus)
    steps, sstats = comp.compose_sharded(corpus, num_shards=4)
    assert len(steps) == len(batches)
    for st, cb in zip(steps, batches):
        union = np.concatenate([r.sample_ids for r in st.replicas])
        assert set(union[union >= 0]) == set(cb.sample_ids)
    assert ustats.hit_rate > 0                 # corpus manufactures hits
    for r_rate in sstats.replica_hit_rate:
        assert r_rate >= ustats.hit_rate - 1e-9


def test_compose_sharded_small_corpus_pads_with_fillers():
    corpus = [chain(3), chain(3), chain(5)]
    comp = BatchComposer(8)
    steps, stats = comp.compose_sharded(corpus, num_shards=4)
    assert len(steps) == 1
    st = steps[0]
    assert all(len(r.graphs) == 1 for r in st.replicas)
    ids = np.concatenate([r.sample_ids for r in st.replicas])
    assert np.array_equal(np.sort(ids[ids >= 0]), np.arange(3))
    assert stats.num_fillers == 1
    assert stats.num_shards == 4 and stats.num_steps == 1


def test_compose_sharded_validates():
    comp = BatchComposer(10)
    with pytest.raises(ValueError, match="divisible"):
        comp.compose_sharded([chain(2)], num_shards=4)
    comp = BatchComposer(8)
    with pytest.raises(ValueError, match="empty"):
        comp.compose_sharded([], num_shards=4)
    with pytest.raises(ValueError, match="reserved"):
        comp.compose_sharded([chain(2)], aux={"weights": [1.0]},
                             num_shards=4)
    with pytest.raises(ValueError, match="reserved"):
        comp.compose_sharded([chain(2)], aux={"sample_ids": [0]},
                             num_shards=4)
    with pytest.raises(ValueError, match="num_shards"):
        comp.compose_sharded([chain(2)], num_shards=0)


# ---------------------------------------------------------------------------
# Properties: lossless permutation, rider alignment, pad bounds
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    _corpus_params = st.tuples(
        st.integers(min_value=0, max_value=2 ** 32 - 1),  # corpus seed
        st.integers(min_value=1, max_value=23),           # corpus size
        st.integers(min_value=1, max_value=7),            # batch size
        st.sampled_from(["multiple", "pow2", "tight"]),   # bucketing
    )
else:                                     # pragma: no cover - skip shim
    _corpus_params = None


@given(_corpus_params)
@settings(max_examples=40, deadline=None)
def test_composer_is_lossless_permutation(params):
    seed, n, bs, mode = params
    rng = np.random.default_rng(seed)
    corpus = _random_corpus(rng, n)
    inputs = [rng.standard_normal((g.num_nodes, 2)).astype(np.float32)
              for g in corpus]
    aux = {"labels": [int(rng.integers(10)) for _ in range(n)],
           "tags": [f"s{i}" for i in range(n)]}
    policy = None if mode == "tight" else BucketPolicy(mode=mode)
    comp = BatchComposer(bs, bucket_policy=policy)
    batches, stats = comp.compose(corpus, inputs, aux)

    # exact permutation: every sample exactly once, none invented
    ids = np.concatenate([b.sample_ids for b in batches])
    assert sorted(ids.tolist()) == list(range(n))
    assert stats.num_samples == n
    assert stats.num_batches == len(batches)

    for b in batches:
        assert 1 <= len(b) <= bs
        for j, i in enumerate(b.sample_ids):
            # graphs/inputs/riders all aligned with their sample id
            assert b.graphs[j] is corpus[i]
            assert b.inputs[j] is inputs[i]
            assert b.aux["labels"][j] == aux["labels"][i]
            assert b.aux["tags"][j] == aux["tags"][i]
        # the batch fits its planned bucket (pads dominate tight dims);
        # pack_batch at those pads must therefore never raise
        if b.pads is not None:
            t, m, a, nn = tight_dims(b.graphs)
            assert b.pads.levels >= t and b.pads.width >= m
            assert b.pads.arity >= a and b.pads.nodes >= nn
            s = pack_batch(b.graphs, *b.pads)
            assert (s.T, s.M, s.A, s.N) == tuple(b.pads)


@given(st.integers(min_value=0, max_value=2 ** 32 - 1))
@settings(max_examples=25, deadline=None)
def test_composer_groups_manufacture_hits(seed):
    """Duplicate-heavy corpora compose whole same-fingerprint batches:
    the predicted hit rate is positive and at least FIFO's, and feeding
    the composed epoch through a real cache reproduces it exactly."""
    rng = np.random.default_rng(seed)
    corpus = _random_corpus(rng, 24, dup_frac=0.8)
    policy = BucketPolicy(mode="pow2")
    comp = BatchComposer(4, bucket_policy=policy)
    batches, stats = comp.compose(corpus)
    fifo = fifo_stats(corpus, 4, policy)
    assert stats.hit_rate >= fifo.hit_rate
    cache = ScheduleCache(enabled=True, persist=False)
    for b in batches:
        cache.get_or_pack(b.graphs, b.pads)
    assert cache.hit_rate == pytest.approx(stats.hit_rate)


# ---------------------------------------------------------------------------
# Deterministic units
# ---------------------------------------------------------------------------

def test_composer_validates_inputs():
    with pytest.raises(ValueError, match="batch_size"):
        BatchComposer(0)
    with pytest.raises(ValueError, match="shape_budget"):
        BatchComposer(2, shape_budget=0)
    comp = BatchComposer(2)
    with pytest.raises(ValueError, match="empty corpus"):
        comp.compose([])
    with pytest.raises(ValueError, match="2 inputs for 3 graphs"):
        comp.compose([chain(2)] * 3, [np.zeros((2, 1))] * 2)
    with pytest.raises(ValueError, match="aux rider 'labels'"):
        comp.compose([chain(2)] * 3, aux={"labels": [0, 1]})
    with pytest.raises(ValueError, match="'sample_ids' is reserved"):
        comp.compose([chain(2)] * 3, aux={"sample_ids": [0, 1, 2]})


def test_composer_singleton_and_leftovers():
    # 5 copies of one shape + 1 odd one, bs=2: two whole-group batches,
    # then a leftover batch of the group's 5th copy + the odd sample.
    corpus = [chain(4) for _ in range(5)] + [chain(9)]
    comp = BatchComposer(2, bucket_policy=None)
    batches, stats = comp.compose(corpus)
    assert stats.num_batches == 3
    assert stats.group_batches == 2
    assert stats.leftover_batches == 1
    sizes = sorted(len(b) for b in batches)
    assert sizes == [2, 2, 2]
    ids = np.concatenate([b.sample_ids for b in batches])
    assert sorted(ids.tolist()) == list(range(6))
    # singleton corpus: one batch of one
    batches, stats = BatchComposer(3).compose([chain(3)])
    assert len(batches) == 1 and len(batches[0]) == 1


def test_composer_deterministic_across_epochs():
    """Same corpus → identical plan (the property cross-epoch cache
    hits rely on)."""
    rng = np.random.default_rng(7)
    corpus = _random_corpus(rng, 17)
    comp = BatchComposer(4)
    b1, s1 = comp.compose(corpus)
    b2, s2 = comp.compose(corpus)
    assert s1 == s2
    for x, y in zip(b1, b2):
        np.testing.assert_array_equal(x.sample_ids, y.sample_ids)
        assert x.pads == y.pads


def test_composer_shape_budget_consolidation():
    rng = np.random.default_rng(3)
    corpus = _random_corpus(rng, 40, dup_frac=0.3)
    policy = BucketPolicy(mode="pow2")
    free = BatchComposer(4, bucket_policy=policy)
    free_batches, free_stats = free.compose(corpus)
    budget = max(1, free_stats.compiled_shapes - 1)
    capped = BatchComposer(4, bucket_policy=policy, shape_budget=budget)
    batches, stats = capped.compose(corpus)
    # merging is only legal within an arity class (fixed-arity cells),
    # so the reachable floor is one shape per distinct arity
    arity_floor = len({b.pads.arity for b in free_batches})
    assert stats.compiled_shapes <= max(budget, arity_floor)
    assert stats.compiled_shapes < free_stats.compiled_shapes
    # consolidation may only pad UP — every batch still fits its bucket
    for b in batches:
        t, m, a, nn = tight_dims(b.graphs)
        assert b.pads.levels >= t and b.pads.width >= m
        assert b.pads.nodes >= nn and b.pads.arity >= a


def _skewed_corpus(n_samples: int, seed: int = 0):
    """Real-traffic skew: five HOT topologies carry ~75% of the mass
    (Zipf-ish within), a long tail of fresh random shapes the rest,
    in shuffled arrival order."""
    rng = np.random.default_rng(seed)
    hot = [random_binary_tree(int(s), np.random.default_rng(100 + i))
           for i, s in enumerate((6, 6, 10, 14, 22))]
    corpus = []
    for _ in range(n_samples):
        if rng.random() < 0.75:
            corpus.append(hot[min(int(rng.zipf(1.6)) - 1, len(hot) - 1)])
        else:
            corpus.append(random_binary_tree(int(rng.integers(2, 28)), rng))
    rng.shuffle(corpus)
    return corpus


def _epoch_occupancy(plan, pipe: SchedulePipeline) -> float:
    occ = []
    for graphs, pads in plan:
        inputs = [np.zeros((g.num_nodes, 1), np.float32) for g in graphs]
        occ.append(pipe.pack(graphs, inputs, pads=pads).sched.occupancy)
    return float(np.mean(occ))


def test_composed_epoch_beats_fifo_on_skewed_corpus():
    """Over one epoch of 256 skewed samples in batches of 16, composed
    batches beat FIFO slicing on measured cache hit rate AND padded
    occupancy, with no more compiled shapes (the composer's shape
    budget is FIFO's count)."""
    corpus = _skewed_corpus(256)
    bs = 16
    policy = BucketPolicy(mode="pow2")
    pipe_fifo = SchedulePipeline(
        1, bucket_policy=policy,
        cache=ScheduleCache(enabled=True, persist=False))
    fifo_occ = _epoch_occupancy(
        [(corpus[i: i + bs], "policy") for i in range(0, len(corpus), bs)],
        pipe_fifo)
    composer = BatchComposer(
        bs, bucket_policy=policy,
        shape_budget=pipe_fifo.stats()["compiled_shapes"])
    composed, _ = composer.compose(corpus)
    pipe_comp = SchedulePipeline(
        1, bucket_policy=policy,
        cache=ScheduleCache(enabled=True, persist=False))
    comp_occ = _epoch_occupancy([(b.graphs, b.pads) for b in composed],
                                pipe_comp)
    f, c = pipe_fifo.stats(), pipe_comp.stats()
    assert c["hit_rate"] > f["hit_rate"], (c["hit_rate"], f["hit_rate"])
    assert comp_occ > fifo_occ, (comp_occ, fifo_occ)
    assert c["compiled_shapes"] <= f["compiled_shapes"]


def test_compose_iter_feeds_prefetch():
    """compose_iter yields the 4-tuple item shape the pipeline's async
    stage consumes, pads included."""
    rng = np.random.default_rng(13)
    corpus = _random_corpus(rng, 8)
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) for g in corpus]
    pipe = SchedulePipeline(INPUT_DIM, bucket_policy=BucketPolicy(),
                            cache=ScheduleCache(enabled=True,
                                                persist=False))
    comp = pipe.composer(3)
    expected, _ = comp.compose(corpus, inputs)
    stream = pipe.prefetch(comp.compose_iter(corpus, inputs))
    got = list(stream)
    stream.close()
    assert len(got) == len(expected)
    for pb, cb in zip(got, expected):
        assert (pb.sched.T, pb.sched.M, pb.sched.A, pb.sched.N) == \
            tuple(cb.pads)                # composer pads honoured
        np.testing.assert_array_equal(pb.aux["sample_ids"],
                                      cb.sample_ids)


def test_composed_batch_source_cycles_epochs():
    rng = np.random.default_rng(11)
    corpus = _random_corpus(rng, 9)
    inputs = [rng.standard_normal((g.num_nodes, 2)).astype(np.float32)
              for g in corpus]
    src = ComposedBatchSource(corpus, inputs, {"y": list(range(9))},
                              composer=BatchComposer(4), epochs=2)
    items = list(src)
    assert src.stats is not None
    per_epoch = src.stats.num_batches
    assert len(items) == 2 * per_epoch
    ids = np.concatenate([it[2]["sample_ids"] for it in items])
    assert sorted(ids.tolist()) == sorted(list(range(9)) * 2)
    for g, x, aux, pads in items:
        assert len(g) == len(aux["y"]) == len(aux["sample_ids"])


# ---------------------------------------------------------------------------
# Serving: compose pending requests before flush
# ---------------------------------------------------------------------------

def test_structure_serve_engine_composes_pending_requests():
    rng = np.random.default_rng(5)
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))
    shape_a = random_binary_tree(4, np.random.default_rng(0))
    shape_b = random_binary_tree(7, np.random.default_rng(1))

    def mk(i, shape):
        g = InputGraph(children=[list(c) for c in shape.children])
        x = rng.standard_normal((g.num_nodes, INPUT_DIM)).astype(np.float32)
        return StructureRequest(i, g, x)

    # irregular arrival: FIFO pairs are mostly mixed (few repeated
    # batch fingerprints); composed flushes group same-shape requests
    # into recurring whole batches.
    arrival = "bbaaabaabaaa"
    reqs = [mk(i, shape_a if c == "a" else shape_b)
            for i, c in enumerate(arrival)]

    def pinned_pipeline():
        # disk tier pinned OFF so the comparison holds under
        # REPRO_SCHED_PERSIST
        return SchedulePipeline(
            INPUT_DIM, bucket_policy=BucketPolicy(mode="pow2"),
            cache=ScheduleCache(enabled=True, persist=False))

    fifo = StructureServeEngine(fn, params, batch_size=2, compose=False,
                                pipeline=pinned_pipeline())
    for i, c in enumerate(arrival):
        fifo.submit(mk(i, shape_a if c == "a" else shape_b))
    fifo.run()
    composed = StructureServeEngine(fn, params, batch_size=2,
                                    pipeline=pinned_pipeline())
    for r in reqs:
        composed.submit(r)
    done = composed.run()
    assert len(done) == len(arrival)
    assert {r.request_id for r in done} == set(range(len(arrival)))
    # same-shape batches hit the schedule cache; FIFO's mixed ones miss
    assert composed.pipeline.cache.hits > fifo.pipeline.cache.hits
    assert composed.pipeline.cache.hits >= 4
    # oldest request anchors every flush: first batch serves request 0
    first = composed.finished[:2]
    assert 0 in {r.request_id for r in first}


def test_structure_serve_engine_rejects_duplicate_submission():
    """The flush path tracks queue entries by identity and the engine
    fills requests in place, so one request object may be pending at
    most once — a re-submission is REJECTED (counted, returns False)
    without disturbing the original's pending lifecycle."""
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))
    eng = StructureServeEngine(fn, params)
    g = random_binary_tree(2, np.random.default_rng(0))
    req = StructureRequest(0, g, np.zeros((g.num_nodes, INPUT_DIM),
                                          np.float32))
    assert eng.submit(req) is True
    assert eng.submit(req) is False
    assert req.status == "pending" and len(eng.queue) == 1
    assert eng.health()["rejected"] == 1
    done = eng.run()
    assert len(done) == 1 and done[0].status == "ok"


def test_structure_serve_engine_compose_matches_fifo_results():
    rng = np.random.default_rng(9)
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))
    graphs = [random_binary_tree(int(rng.integers(2, 6)), rng)
              for _ in range(8)]
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) for g in graphs]
    results = {}
    for compose in (False, True):
        eng = StructureServeEngine(fn, params, batch_size=3,
                                   compose=compose)
        for i, (g, x) in enumerate(zip(graphs, inputs)):
            eng.submit(StructureRequest(i, g, x))
        for r in eng.run():
            results.setdefault(r.request_id, []).append(r.root_state)
    for rid, (a, b) in results.items():
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# End-to-end order independence: composed epoch ≡ FIFO epoch, per sample
# ---------------------------------------------------------------------------

def _epoch_per_sample(batch_items, fn, params, pads, mode, impl,
                      monkeypatch):
    """Per-sample losses and per-sample external-input grads over an
    epoch, keyed by original sample id, plus the epoch-summed param
    grads.  All batches packed at the same ``pads`` (one program)."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", impl)
    losses, ext_grads = {}, {}
    param_sum = None
    for graphs, inputs, ids in batch_items:
        sched = pack_batch(graphs, *pads)
        dev = sched.to_device()
        ext = jnp.asarray(pack_external(inputs, sched, INPUT_DIM))

        def loss(p, e):
            buf = execute(fn, p, dev, e, fusion_mode=mode).buf
            per = jnp.sum(readout_roots(buf, dev) ** 2, axis=-1)  # [K]
            return jnp.sum(per), per

        (_, per), (gp, ge) = jax.value_and_grad(
            loss, (0, 1), has_aux=True)(params, ext)
        per = np.asarray(per)
        ge = np.asarray(ge)
        N = sched.N
        for k, i in enumerate(ids):
            losses[int(i)] = per[k]
            ext_grads[int(i)] = ge[k * N: k * N + graphs[k].num_nodes]
        gp_np = jax.tree.map(np.asarray, gp)
        param_sum = gp_np if param_sum is None else jax.tree.map(
            np.add, param_sum, gp_np)
    return losses, ext_grads, param_sum


@pytest.mark.parametrize("mode,impl", [
    ("none", "chunked"),                 # unfused op-by-op leg
    ("megastep", "chunked"),             # fused VJP, jnp sweep
    ("megastep", "pallas"),              # fused VJP, one launch per level
])
def test_composed_epoch_order_independence(mode, impl, monkeypatch):
    """The acceptance criterion: composing an epoch is invisible to
    every individual sample.  Per-sample losses and per-sample
    external-input gradients are BIT-IDENTICAL between a FIFO epoch and
    a composed epoch after realignment by sample id, on all three
    execution legs; epoch-summed parameter grads agree to float32
    roundoff (their slot reduction order legitimately moves with the
    permutation)."""
    rng = np.random.default_rng(21)
    corpus = _random_corpus(rng, 12, dup_frac=0.6)
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) * 0.3 for g in corpus]
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=4, arity=2)
    params = fn.init(jax.random.PRNGKey(0))
    bs = 4

    fifo_items = []
    for i in range(0, len(corpus), bs):
        ids = list(range(i, i + bs))
        fifo_items.append(([corpus[j] for j in ids],
                           [inputs[j] for j in ids], ids))
    comp = BatchComposer(bs, bucket_policy=BucketPolicy())
    batches, _ = comp.compose(corpus, inputs)
    comp_items = [(b.graphs, b.inputs, b.sample_ids.tolist())
                  for b in batches]
    assert any(b.sample_ids.tolist() != f[2]
               for b, f in zip(batches, fifo_items))  # actually reordered

    # one shared bucket covering every batch on both legs: identical
    # compiled program, so any difference is composition's fault
    dims = np.array([tight_dims(it[0]) for it in fifo_items + comp_items])
    pads = PadDims(*(int(x) for x in dims.max(axis=0)))

    f_loss, f_ext, f_param = _epoch_per_sample(
        fifo_items, fn, params, pads, mode, impl, monkeypatch)
    c_loss, c_ext, c_param = _epoch_per_sample(
        comp_items, fn, params, pads, mode, impl, monkeypatch)

    assert sorted(c_loss) == sorted(f_loss) == list(range(len(corpus)))
    for i in range(len(corpus)):
        np.testing.assert_array_equal(f_loss[i], c_loss[i])
        np.testing.assert_array_equal(f_ext[i], c_ext[i])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-5, atol=1e-6), f_param, c_param)
