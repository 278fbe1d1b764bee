"""Per-graph partial-schedule splicing (PR 10 tentpole): harvesting a
graph's TIGHT solo schedule out of a packed batch and SPLICING cached
solos into a never-seen batch combination must be BYTE-IDENTICAL to the
monolithic ``pack_batch`` — every array, sorted-run arrays included —
and end-to-end consumers (``Trainer`` losses/grads, the continuous
engine's served states) must be bitwise indistinguishable between the
spliced and cold-packed paths on both fusion legs."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.scheduler import execute, readout_nodes, readout_roots
from repro.core.structure import (InputGraph, balanced_binary_tree, chain,
                                  pack_batch, pack_external,
                                  random_binary_tree, random_dag)
from repro.models.treelstm import TreeLSTMVertex
from repro.pipeline import (ScheduleCache, extract_solo, graph_fingerprint,
                            splice_schedules)
from repro.serve import ContinuousBatchEngine, ContinuousRequest
from repro.train import MetricLogger, TrainConfig, Trainer

from tests.hypothesis_compat import given, settings, st

INPUT_DIM = 4

_SCHED_FIELDS = ("child_ids", "child_mask", "ext_ids", "node_mask",
                 "slot_of", "node_valid", "root_slots", "num_nodes",
                 "sort_perm", "sorted_child_ids", "run_head")


def _assert_sched_equal(got, want):
    for f in _SCHED_FIELDS:
        a, b = getattr(got, f), getattr(want, f)
        assert (a is None) == (b is None), f
        if a is not None:
            np.testing.assert_array_equal(a, b, err_msg=f)


def _rand_graph(rng) -> InputGraph:
    kind = int(rng.integers(0, 4))
    if kind == 0:
        return chain(int(rng.integers(1, 8)))
    if kind == 1:
        return random_binary_tree(int(rng.integers(1, 8)), rng)
    if kind == 2:
        return random_dag(int(rng.integers(1, 9)), rng, max_arity=3)
    return balanced_binary_tree(2 ** int(rng.integers(0, 4)))


def _forest(rng, k):
    return [_rand_graph(rng) for _ in range(k)]


def _pads_for(graphs, which, rng):
    if which == "tight":
        return None
    s = pack_batch(graphs)
    if which == "padded":
        return (s.T + int(rng.integers(1, 3)), s.M + int(rng.integers(1, 4)),
                s.A, s.N + int(rng.integers(1, 3)))
    return (s.T, s.M, s.A + 1, s.N)      # "arity": widen A only


# ---------------------------------------------------------------------------
# Byte-identity: harvest and splice vs monolithic pack_batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("with_runs", [True, False])
def test_splice_byte_identical_to_pack_batch(seed, with_runs):
    """The contract: for random forests (chains, trees, dup-child DAGs,
    singleton graphs, K=1) under tight and padded dims, splicing the
    members' solo schedules reproduces the monolithic ``pack_batch``
    byte for byte — sorted-run arrays included."""
    rng = np.random.default_rng(seed)
    for which in ("tight", "padded", "arity"):
        graphs = _forest(rng, int(rng.integers(1, 6)))
        pads = _pads_for(graphs, which, rng)
        mono = pack_batch(graphs, *(pads or (None,) * 4),
                          with_runs=with_runs)
        solos = [pack_batch([g], with_runs=False) for g in graphs]
        spliced = splice_schedules(graphs, solos, pads, with_runs=with_runs)
        _assert_sched_equal(spliced, mono)


@pytest.mark.parametrize("seed", range(8))
def test_harvest_byte_identical_to_solo_pack(seed):
    """``extract_solo`` projects each member's TIGHT solo schedule out
    of the batch arrays — identical to packing that graph alone."""
    rng = np.random.default_rng(100 + seed)
    graphs = _forest(rng, int(rng.integers(1, 6)))
    batch = pack_batch(graphs)
    for k, g in enumerate(graphs):
        solo = extract_solo(batch, k)
        _assert_sched_equal(solo, pack_batch([g], with_runs=False))


def test_extract_solo_is_pad_tolerant():
    """Harvest works from BUCKETED cold packs too: the contiguous-lane
    invariant survives padding, so the recovered solo is still the
    tight pack — and out-of-range indices raise."""
    graphs = [chain(3), chain(5)]
    s = pack_batch(graphs, pad_levels=8, pad_width=4, pad_arity=2,
                   pad_nodes=16)
    for k, g in enumerate(graphs):
        _assert_sched_equal(extract_solo(s, k),
                            pack_batch([g], with_runs=False))
    with pytest.raises(ValueError, match="out of range"):
        extract_solo(s, 2)


def test_splice_rejects_undersized_pads():
    graphs = [chain(3), chain(5)]
    solos = [pack_batch([g], with_runs=False) for g in graphs]
    with pytest.raises(ValueError, match="pad_nodes"):
        splice_schedules(graphs, solos, (None, None, None, 4))


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_property_splice_byte_identity(data):
    """Hypothesis sweep of the same contract over drawn forests and
    pad choices (runs when hypothesis is installed; the deterministic
    sweep above keeps coverage without it)."""
    seed = data.draw(st.integers(min_value=0, max_value=2 ** 31 - 1))
    rng = np.random.default_rng(seed)
    graphs = _forest(rng, data.draw(st.integers(min_value=1, max_value=5)))
    which = data.draw(st.sampled_from(["tight", "padded", "arity"]))
    with_runs = data.draw(st.booleans())
    pads = _pads_for(graphs, which, rng)
    mono = pack_batch(graphs, *(pads or (None,) * 4), with_runs=with_runs)
    solos = [pack_batch([g], with_runs=False) for g in graphs]
    _assert_sched_equal(
        splice_schedules(graphs, solos, pads, with_runs=with_runs), mono)


# ---------------------------------------------------------------------------
# Cache integration: harvest on cold pack, splice on new combinations
# ---------------------------------------------------------------------------

def test_cache_splices_new_combination_of_seen_graphs():
    """A never-seen batch whose members were all harvested from earlier
    cold packs is assembled by the graph tier — zero ``pack_batch``
    calls — and is byte-identical to the cold pack it replaced."""
    rng = np.random.default_rng(7)
    graphs = _forest(rng, 4)
    cache = ScheduleCache(enabled=True, persist=False, splice=True)
    cache.get_or_pack(graphs[:2])        # cold: packs + harvests members
    cache.get_or_pack(graphs[2:])
    assert cache.packs == 2 and cache.harvests >= 2
    combo = [graphs[2], graphs[0], graphs[3]]
    s = cache.get_or_pack(combo)
    assert cache.splices == 1 and cache.packs == 2     # no third pack
    _assert_sched_equal(s, pack_batch(combo))
    # the spliced result lands in the batch LRU: the re-lookup is a hit
    assert cache.get_or_pack(combo) is s
    assert cache.hits == 1


def test_cache_splice_respects_pads_and_duplicates():
    rng = np.random.default_rng(8)
    g = random_dag(6, rng, max_arity=2)
    h = chain(4)
    cache = ScheduleCache(enabled=True, persist=False, splice=True)
    cache.get_or_pack([g, h])
    pads = (8, 8, 2, 8)
    s = cache.get_or_pack([h, g, h], pads)             # dup member + pads
    assert cache.splices == 1
    _assert_sched_equal(s, pack_batch([h, g, h], *pads))


def test_cache_graph_tier_repads_solo_from_tight_entry():
    """A padded solo lookup (the continuous engine's bucketed admission)
    of a graph seen only inside a cold BATCH pack is served by a K=1
    splice of the harvested tight solo — no topology walk."""
    rng = np.random.default_rng(9)
    g = random_dag(5, rng, max_arity=2)
    cache = ScheduleCache(enabled=True, persist=False, splice=True)
    cache.get_or_pack([g, chain(3)])                   # harvests g (tight)
    pads = (8, 4, 2, 8)
    solo = cache.get_or_pack_graph(g, pads)
    assert cache.splices == 1 and cache.graph_packs == 0
    _assert_sched_equal(solo, pack_batch([g], *pads, with_runs=False))
    # a training-path re-lookup upgrades the cached entry with runs
    solo_r = cache.get_or_pack_graph(g, pads, with_runs=True)
    assert cache.graph_packs == 0
    _assert_sched_equal(solo_r, pack_batch([g], *pads))


def test_every_unseen_zipf_combination_splices_without_packing():
    """On a Zipf-weighted corpus (24 trees of 32-128 nodes, 16 unseen
    batch combinations of 16), a cache whose graph tier holds every
    topology assembles every combination by splicing: zero
    ``pack_batch`` calls."""
    rng = np.random.default_rng(0)
    n_topologies, bs, n_combos = 24, 16, 16
    topos = [random_binary_tree(int(rng.integers(32, 128)), rng)
             for _ in range(n_topologies)]
    zipf = 1.0 / np.arange(1, n_topologies + 1) ** 1.2
    zipf /= zipf.sum()
    combos, seen = [], set()
    while len(combos) < n_combos:
        idx = tuple(int(i) for i in rng.choice(n_topologies, bs, p=zipf))
        if idx not in seen:
            seen.add(idx)
            combos.append([topos[i] for i in idx])
    cache = ScheduleCache(enabled=True, persist=False)
    for g in topos:
        cache.get_or_pack([g], with_runs=False)
    cache.reset_stats()
    for c in combos:
        cache.get_or_pack(c, with_runs=False)
    s = cache.stats()
    assert s["splices"] == n_combos and s["packs"] == 0, s


def test_splice_env_gate_disables_graph_tier():
    rng = np.random.default_rng(10)
    graphs = _forest(rng, 3)
    cache = ScheduleCache(enabled=True, persist=False, splice=False)
    assert not cache.splice
    cache.get_or_pack(graphs[:2])
    assert cache.harvests == 0
    cache.get_or_pack([graphs[1], graphs[0]])
    assert cache.splices == 0 and cache.packs == 2     # plain cold pack
    assert ScheduleCache(enabled=True, persist=False).splice   # default


def test_warm_restart_splices_from_per_graph_disk_entries(tmp_path):
    """ISSUE acceptance: a fresh process with a warm store splices a
    NEVER-SEEN combination straight from per-graph disk entries —
    zero ``pack_batch`` executions of any kind."""
    rng = np.random.default_rng(11)
    graphs = _forest(rng, 4)
    cold = ScheduleCache(enabled=True, persist=tmp_path, splice=True)
    cold.get_or_pack(graphs[:2])
    cold.get_or_pack(graphs[2:])
    combo = [graphs[3], graphs[1], graphs[0]]
    warm = ScheduleCache(enabled=True, persist=tmp_path, splice=True)
    s = warm.get_or_pack(combo)
    assert warm.packs == 0 and warm.graph_packs == 0
    assert warm.splices == 1
    assert warm.graph_disk_hits == len({graph_fingerprint(g)
                                        for g in combo})
    _assert_sched_equal(s, pack_batch(combo))


def test_spliced_batches_are_not_written_to_batch_store(tmp_path):
    """Spliced results stay out of the batch disk tier: the per-graph
    entries already cover every combination, so persisting each combo
    would grow the store combinatorially for zero extra warm hits."""
    rng = np.random.default_rng(12)
    graphs = _forest(rng, 3)
    cache = ScheduleCache(enabled=True, persist=tmp_path, splice=True)
    cache.get_or_pack(graphs)
    stores_after_cold = cache.persist.stores
    cache.get_or_pack(graphs[::-1])                    # spliced
    assert cache.splices == 1
    assert cache.persist.stores == stores_after_cold


# ---------------------------------------------------------------------------
# End-to-end bit-identity: Trainer and the continuous engine
# ---------------------------------------------------------------------------

MODES = ["none", "megastep"]


def _train(fn, dev, ext, mode, steps=3):
    # dev is closed over (schedules are static data, not batch pytrees);
    # ext rides in params so the schedule's backward gather is exercised.
    def loss_fn(p, batch):
        buf = execute(fn, p["vertex"], dev, p["ext"], fusion_mode=mode).buf
        l = jnp.sum(readout_nodes(buf, dev) ** 2) \
            + jnp.sum(readout_roots(buf, dev) ** 3)
        return l, {"loss2": l}

    def init(key):
        # fresh buffers: the train step donates params, and ext is shared
        # between the monolithic and spliced runs
        return {"vertex": fn.init(jax.random.PRNGKey(0)),
                "ext": jnp.array(np.asarray(ext))}

    tr = Trainer(loss_fn, init,
                 TrainConfig(lr=0.01, warmup_steps=1, weight_decay=0.0,
                             total_steps=steps, log_every=1))
    state = tr.init_state(jax.random.PRNGKey(0))

    def stream():
        while True:
            yield {"step": jnp.zeros(())}

    state, logger = tr.fit(state, stream(), steps=steps,
                           logger=MetricLogger(log_fn=lambda *_: None))
    return state, [h["loss"] for h in logger.history]


@pytest.mark.parametrize("mode", MODES)
def test_trainer_bit_identical_on_spliced_schedules(mode):
    """Training on SPLICED schedules is bitwise indistinguishable from
    training on monolithic cold packs: identical per-step losses and
    identical final parameters, on the unfused and fused legs."""
    rng = np.random.default_rng(13)
    graphs = [random_dag(int(rng.integers(2, 6)), rng, max_arity=2)
              for _ in range(3)]
    inputs = [rng.standard_normal((g.num_nodes, INPUT_DIM))
              .astype(np.float32) * 0.3 for g in graphs]
    fn = TreeLSTMVertex(input_dim=INPUT_DIM, hidden=3, arity=2)

    mono = pack_batch(graphs, pad_arity=2)
    cache = ScheduleCache(enabled=True, persist=False, splice=True)
    for g in graphs:                      # seen solo → harvested combos
        cache.get_or_pack([g], (None, None, 2, None))
    spliced = cache.get_or_pack(graphs, (None, None, 2, None))
    assert cache.splices == 1
    _assert_sched_equal(spliced, mono)

    ext = jnp.asarray(pack_external(inputs, mono, INPUT_DIM))
    st_m, losses_m = _train(fn, mono.to_device(), ext, mode)
    st_s, losses_s = _train(fn, spliced.to_device(), ext, mode)
    assert losses_m == losses_s
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), st_m.params, st_s.params)


@pytest.mark.parametrize("mode", MODES)
def test_engine_bit_identical_with_warm_graph_tier(mode):
    """Serving through a cache whose graph tier was warmed by training
    cold packs (admission solos arrive via K=1 splices, zero packs)
    yields root states bitwise equal to a cold engine's."""
    rng = np.random.default_rng(14)
    graphs = [chain(int(rng.integers(1, 7))) for _ in range(5)]
    inputs = [rng.standard_normal((g.num_nodes, 4)).astype(np.float32) * 0.4
              for g in graphs]
    from repro.models.rnn import LSTMVertex
    fn = LSTMVertex(input_dim=4, hidden=3)
    params = fn.init(jax.random.PRNGKey(0))

    def serve(cache):
        eng = ContinuousBatchEngine(fn, params, num_rows=16,
                                    frontier_width=3, fusion_mode=mode,
                                    cache=cache)
        reqs = [ContinuousRequest(i, g, x)
                for i, (g, x) in enumerate(zip(graphs, inputs))]
        for r in reqs:
            assert eng.submit(r), r.error
        eng.run()
        assert all(r.status == "ok" for r in reqs)
        return [r.root_state for r in reqs]

    warm_cache = ScheduleCache(enabled=True, persist=False, splice=True)
    warm_cache.get_or_pack(graphs)        # one cold pack harvests all
    warm_cache.reset_stats()
    warm = serve(warm_cache)
    assert warm_cache.graph_packs == 0    # admissions were K=1 splices
    assert warm_cache.splices >= 1

    cold = serve(ScheduleCache(enabled=True, persist=False, splice=True))
    for a, b in zip(warm, cold):
        np.testing.assert_array_equal(a, b)
