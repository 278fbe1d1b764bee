"""Mixed-depth union frontiers (core.scheduler.frontier_step): random
graph cohorts executed at STAGGERED depths through a shared arena must
produce per-graph states bitwise equal to depth-aligned (solo batched)
execution, on both fusion legs — the primitive underneath
``serve.continuous.ContinuousBatchEngine``'s bit-identity contract."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.scheduler import execute, frontier_step, resolve_fusion
from repro.core.structure import chain, pack_batch, pack_external, random_dag
from repro.core.vertex import has_eager_projection
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.level_megastep import as_rows, from_rows
from repro.models.rnn import GRUVertex, LSTMVertex
from repro.models.treelstm import TreeLSTMVertex


def _solo(fn, params, g, x, fusion_mode):
    """Reference: one graph scored alone through the level scan (same
    arity padding as the frontier plans, so slot numbering matches)."""
    sched = pack_batch([g], pad_arity=max(1, getattr(fn, "arity", 1)),
                       with_runs=False)
    ext = jnp.asarray(pack_external([x], sched, fn.input_dim))
    dev = sched.to_device()
    buf = np.asarray(execute(fn, params, dev, ext,
                             fusion_mode=fusion_mode).buf)
    return sched, buf


def _frontier_levels(fn, params, g, x, arity):
    """A graph's per-level frontier data in SOLO-slot space, external
    rows pre-gathered (projected when the cell declares a projection) —
    what the continuous engine derives at admission."""
    sched = pack_batch([g], pad_arity=arity, with_runs=False)
    raw = pack_external([x], sched, fn.input_dim)
    if has_eager_projection(fn):
        # jitted, like the engine's admission path (and like solo
        # execute's in-jit hoist) — eager projection rounds differently.
        ext = np.asarray(jax.jit(fn.project_inputs)(params,
                                                    jnp.asarray(raw)))
    else:
        ext = raw
    T, M = sched.T, sched.M
    levels = []
    for t in range(T):
        lanes = np.nonzero(sched.node_mask[t] > 0)[0]
        if lanes.size == 0:
            continue
        levels.append(((t * M + lanes).astype(np.int64),
                       sched.child_ids[t][lanes].astype(np.int64),
                       sched.child_mask[t][lanes].astype(np.float32),
                       ext[sched.ext_ids[t][lanes]]))
    return sched, levels


def _run_union(fn, params, cohort, starts, width, spec):
    """Drive ``frontier_step`` over a shared arena: graph i contributes
    its levels starting at tick ``starts[i]`` (the staggered depths),
    at most one level per graph per tick, splitting a level across
    ticks when the frontier is full.  Returns per-graph arena row maps
    and the final arena buffer."""
    arity = max(1, getattr(fn, "arity", 1))
    per_graph = []
    total = 0
    for g, x in cohort:
        sched, levels = _frontier_levels(fn, params, g, x, arity)
        rows = np.arange(total, total + g.num_nodes)
        arena_of = np.full(sched.T * sched.M + 1, -1, np.int64)
        arena_of[np.concatenate([lv[0] for lv in levels])] = rows
        per_graph.append((sched, levels, arena_of))
        total += g.num_nodes
    R = total
    buf = jnp.zeros((R + 1, fn.state_dim), jnp.float32)
    if spec is not None:
        buf = as_rows(buf)          # the fused leg's row layout
    sent = np.int64(R)
    # Jitted like the engine's window (and solo execute's scan body):
    # the tick math must be the compiled leg, not eager dispatch.
    step_jit = jax.jit(functools.partial(frontier_step, fn, spec=spec))

    cursors = [(0, 0)] * len(cohort)
    tick = 0
    while True:
        parts = []
        used = 0
        for i, (sched, levels, arena_of) in enumerate(per_graph):
            if tick < starts[i]:
                continue
            li, lo = cursors[i]
            if li >= len(levels):
                continue
            slots, cids, cmask, erows = levels[li]
            take = min(len(slots) - lo, width - used)
            if take <= 0:
                continue
            sl = slice(lo, lo + take)
            a_cids = arena_of[cids[sl]]
            a_cids[a_cids < 0] = sent          # solo sentinel → arena sentinel
            parts.append((arena_of[slots[sl]], a_cids, cmask[sl], erows[sl]))
            cursors[i] = (li + 1, 0) if lo + take >= len(slots) \
                else (li, lo + take)
            used += take
            if used >= width:
                break
        if not parts and all(c[0] >= len(pg[1])
                             for c, pg in zip(cursors, per_graph)):
            break
        if parts:
            A = parts[0][1].shape[1]
            G = parts[0][3].shape[1]
            child_ids = np.full((width, A), R, np.int32)
            child_mask = np.zeros((width, A), np.float32)
            ext_rows = np.zeros((width, G), np.float32)
            node_mask = np.zeros((width,), np.float32)
            out_ids = R + 1 + np.arange(width, dtype=np.int32)
            o = 0
            for dest, cids, cmask, erows in parts:
                n = len(dest)
                out_ids[o:o + n] = dest
                child_ids[o:o + n] = cids
                child_mask[o:o + n] = cmask
                ext_rows[o:o + n] = erows
                node_mask[o:o + n] = 1.0
                o += n
            buf = step_jit(params, buf, jnp.asarray(child_ids),
                           jnp.asarray(child_mask), jnp.asarray(ext_rows),
                           jnp.asarray(node_mask), jnp.asarray(out_ids))
        tick += 1
        assert tick < 10_000
    return per_graph, np.asarray(from_rows(buf) if spec is not None else buf)


CELLS = [LSTMVertex(input_dim=5, hidden=4),
         GRUVertex(input_dim=5, hidden=4),
         TreeLSTMVertex(input_dim=5, hidden=4, arity=2)]


@pytest.mark.parametrize("fusion_mode", ["none", "megastep"])
@pytest.mark.parametrize("cell_idx", range(len(CELLS)))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_staggered_union_frontier_matches_solo(fusion_mode, cell_idx, seed):
    fn = CELLS[cell_idx]
    params = fn.init(jax.random.PRNGKey(cell_idx))
    rng = np.random.default_rng(seed)
    arity = max(1, getattr(fn, "arity", 1))

    cohort = []
    for _ in range(4):
        n = int(rng.integers(1, 11))
        g = chain(n) if arity == 1 else random_dag(n, rng, max_arity=arity)
        x = rng.standard_normal((n, fn.input_dim)).astype(np.float32) * 0.4
        cohort.append((g, x))
    starts = [int(rng.integers(0, 5)) for _ in cohort]
    width = int(rng.integers(2, 6))

    spec = resolve_fusion(fn, fusion_mode, sched_arity=arity)
    per_graph, arena = _run_union(fn, params, cohort, starts, width, spec)

    for (g, x), (sched, levels, arena_of) in zip(cohort, per_graph):
        _, solo_buf = _solo(fn, params, g, x, fusion_mode)
        for slots, _, _, _ in levels:
            np.testing.assert_array_equal(
                arena[arena_of[slots]], solo_buf[slots],
                err_msg=f"staggered != solo (mode={fusion_mode}, "
                        f"starts={starts}, width={width})")


def test_frontier_megastep_pallas_matches_ref():
    """The pallas dispatch leg (staging-block compose, interpret mode on
    CPU) agrees with the jnp oracle.  Inputs follow the schedule
    contract the kernels assume: an absent child points at the ZERO
    SENTINEL row with mask 0 (the pallas cells do no mask arithmetic —
    a sentinel gather contributes exactly 0), and out-of-range
    destinations occur only on pad lanes (node_mask 0)."""
    rng = np.random.default_rng(0)
    M, A, H, R = 6, 2, 4, 9
    S = 2 * H
    fn = TreeLSTMVertex(input_dim=5, hidden=H, arity=A)
    params = fn.init(jax.random.PRNGKey(0))
    spec = resolve_fusion(fn, "megastep", sched_arity=A)
    weights = spec.weights(params)
    buf = jnp.asarray(rng.standard_normal((R + 1, S)).astype(np.float32)
                      * 0.3).at[R].set(0.0)
    child_mask_np = (rng.random((M, A)) > 0.4).astype(np.float32)
    child_ids_np = np.where(child_mask_np > 0,
                            rng.integers(0, R, (M, A)),
                            R).astype(np.int32)
    node_mask_np = np.ones(M, np.float32)
    node_mask_np[4] = 0.0                       # one pad lane
    out = (R + 1 + np.arange(M)).astype(np.int32)   # pads: out of range
    live = np.nonzero(node_mask_np > 0)[0]
    out[live] = rng.choice(R, live.size, replace=False).astype(np.int32)
    rows = jnp.asarray(rng.standard_normal((M, fn.ext_dim))
                       .astype(np.float32) * 0.3)

    args = (spec.kind, buf, jnp.asarray(child_ids_np),
            jnp.asarray(child_mask_np), rows, jnp.asarray(node_mask_np),
            jnp.asarray(out), weights)
    want = np.asarray(ref.frontier_megastep(*args))
    got = np.asarray(from_rows(kops.frontier_megastep(
        args[0], as_rows(buf), *args[2:], impl="pallas")))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # Neither leg may disturb the zero sentinel.
    np.testing.assert_array_equal(got[R], np.zeros(S, np.float32))
