"""The main path's Pallas kernels compile for a TPU v5e.

Interpret mode (every other kernel test) accepts block shapes, DMAs and
VMEM budgets that the chip's compiler refuses.  These tests compile each
kernel of the training and serving path with ``interpret=False`` against
a *described* v5e — the TPU compiler is installed, no chip is needed —
at the paper widths (H=512, input 256) and at the widest level
``chip_smoke.py`` packs, and check that the program holds the kernel
(``tpu_custom_call``).  A refusal here is what the chip would raise.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and the
fixture skips where no topology can be described.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import gather_scatter as gsc
from repro.kernels import level_megastep as lm
from repro.kernels import level_megastep_bwd as lmb
from repro.kernels import ops

H = 512
#: (M, A, T) per gate kind: the widest levels chip_smoke.py packs —
#: tree_lstm batch 64 (M=1888, 16 levels), var_lstm batch 64 (M=64,
#: 56 levels), tree_fc 16 trees of 256 leaves (M=4096, 16 levels); GRU
#: shares the LSTM chain shapes.
WIDEST = {"lstm": (64, 1, 56), "gru": (64, 1, 56),
          "treelstm": (1888, 2, 16), "treefc": (4096, 2, 16)}
KINDS = sorted(WIDEST)


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off (a described-topology compile cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:           # noqa: BLE001 — no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", old_cache)
        if old_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = old_log


def _dims(kind, A):
    S = {"lstm": 2, "treelstm": 2, "gru": 1, "treefc": 1}[kind] * H
    G = {"lstm": 4, "treelstm": 4, "gru": 3, "treefc": 1}[kind] * H
    ws = {"lstm": [(H, 4 * H), (4 * H,)], "gru": [(H, 3 * H), (3 * H,)],
          "treelstm": [(H, H)] * 4 + [(4 * H,)],
          "treefc": [(A * H, H), (H,)]}[kind]
    return S, G, ws


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("kind", KINDS)
def test_forward_megastep_compiles(kind, one_chip):
    M, A, T = WIDEST[kind]
    S, G, ws = _dims(kind, A)
    R = T * M + 1
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    txt = _compile_text(
        lambda buf, c, e, nm, off, ext, *w: lm.megastep(
            kind, buf, c, e, nm, off, ext, w),
        sd((R, 1, S)), sd((M, A), jnp.int32), sd((M,), jnp.int32),
        sd((M,)), sd((), jnp.int32), sd((R, 1, G)), *[sd(w) for w in ws])
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kind", KINDS)
def test_backward_megastep_compiles(kind, one_chip):
    M, A, T = WIDEST[kind]
    S, G, ws = _dims(kind, A)
    R, n = T * M + 1, M * A
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    txt = _compile_text(
        lambda g, buf, c, e, nm, off, ext, sp, sc, rh, *w: lmb.bwd_megastep(
            kind, g, buf, c, e, nm, off, ext, w, sort_perm=sp,
            sorted_child_ids=sc, run_head=rh),
        sd((R, 1, S)), sd((R, 1, S)), sd((M, A), jnp.int32),
        sd((M,), jnp.int32), sd((M,)), sd((), jnp.int32), sd((R, 1, G)),
        sd((n,), jnp.int32), sd((n,), jnp.int32), sd((n,), jnp.int32),
        *[sd(w) for w in ws])
    assert "tpu_custom_call" in txt


#: (M, A, T) of the training cells' widest buckets with the live flags:
#: Tree-LSTM on SST-length parses (M=4096), LSTM on PTB chains (M=64).
FLAGGED = {"treelstm": (4096, 2, 32), "lstm": (64, 1, 64)}


def _trace_reduce():
    """The benchmark's trace reader, loaded from its file."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "trace_reduce.py")
    spec = importlib.util.spec_from_file_location("trace_reduce", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _kernel_names(compiled):
    """What ``trace_reduce.kernel_of`` makes of each Pallas call of a
    compiled program, printed with its operands' shapes as the chip's
    trace names operations."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    tr = _trace_reduce()
    return [tr.kernel_of(line) for line in text.splitlines()
            if "tpu_custom_call" in line]


@pytest.mark.parametrize("kind", sorted(FLAGGED))
def test_megasteps_with_live_flags_compile(kind, one_chip):
    """Both megasteps with the live flags appended to the ext ids (the
    backward's walk then has a dynamic trip count) compile for the chip,
    and the benchmark still tells them apart by their operands."""
    M, A, T = FLAGGED[kind]
    S, G, ws = _dims(kind, A)
    R, n = T * M + 1, M * A
    L = M + M // lm.block_rows(M) + 1
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    fwd = jax.jit(lambda buf, c, e, nm, off, ext, *w: lm.megastep(
        kind, buf, c, e, nm, off, ext, w)).lower(
        sd((R, 1, S)), sd((M, A), jnp.int32), sd((L,), jnp.int32),
        sd((M,)), sd((), jnp.int32), sd((R, 1, G)),
        *[sd(w) for w in ws]).compile()
    assert _kernel_names(fwd) == ["_megastep_kernel"]
    bwd = jax.jit(
        lambda g, buf, c, e, nm, off, ext, sp, sc, rh, *w: lmb.bwd_megastep(
            kind, g, buf, c, e, nm, off, ext, w, sort_perm=sp,
            sorted_child_ids=sc, run_head=rh)).lower(
        sd((R, 1, S)), sd((R, 1, S)), sd((M, A), jnp.int32),
        sd((L,), jnp.int32), sd((M,)), sd((), jnp.int32), sd((R, 1, G)),
        sd((n,), jnp.int32), sd((n,), jnp.int32), sd((n,), jnp.int32),
        *[sd(w) for w in ws]).compile()
    assert _kernel_names(bwd) == ["_bwd_megastep_kernel"]


def test_scatter_add_rows_compiles(one_chip):
    M, A, T = WIDEST["treelstm"]
    R, n, S = T * M + 1, M * A, 2 * H
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    txt = _compile_text(lambda d, i, r: lmb.scatter_add_rows(d, i, r),
                        sd((R, S)), sd((n,), jnp.int32), sd((n, S)))
    assert "tpu_custom_call" in txt


def test_frontier_megastep_compiles(one_chip, monkeypatch):
    """The pallas leg of ``ops.frontier_megastep`` (staging megastep +
    ``scatter_rows``) at the serving phase's arena and frontier."""
    # The dispatcher picks interpret mode off-TPU; compile the chip leg.
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    rows, width, A = 4097, 256, 2
    S, G, ws = _dims("treelstm", A)
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    txt = _compile_text(
        lambda buf, c, cm, r, nm, oid, *w: ops.frontier_megastep(
            "treelstm", buf, c, cm, r, nm, oid, w, impl="pallas"),
        sd((rows, 1, S)), sd((width, A), jnp.int32), sd((width, A)),
        sd((width, G)), sd((width,)), sd((width,), jnp.int32),
        *[sd(w) for w in ws])
    assert txt.count("tpu_custom_call") >= 2      # megastep + scatter


def test_gather_rows_compiles(one_chip):
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    txt = _compile_text(lambda s, i: gsc.gather_rows(s, i),
                        sd((4097, 1, 2 * H)), sd((256,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_ssm_step_compiles(one_chip, monkeypatch):
    """The Mamba-2 one-token kernel at Granite-4.0-H Micro's published
    widths (64 heads of 64, d_state 128, conv 4) over a 64-lane frontier
    and a 9-layer state arena, the arena aliased in place."""
    from repro.kernels import ssm_step as ks
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    M, R, N, W, K, L = 64, 32, 128, 128, 4, 9
    blk = R * N + (K - 1) * ks.slot_rows(R + 2)
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    compiled = jax.jit(
        lambda a, rows, fl, x, dt, cw, cb, av, dv: ops.ssm_step(
            a, 4, rows, fl, x, dt, cw, cb, av, dv, impl="pallas"),
        donate_argnums=0).lower(
        sd((65, L, blk, W)), sd((M,), jnp.int32),
        sd((M,), jnp.int32), sd((M, R + 2, W)), sd((M, R, W)),
        sd((K, R + 2, W)), sd((R + 2, W)), sd((R, W)), sd((R, W))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the arena is updated in place, never copied
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


def test_treelstm_train_step_compiles(one_chip, monkeypatch):
    """The Tree-LSTM training step at paper widths (hidden 512, input
    300) on a pow2 bucket of the training cell, ``(T, M, K, N)`` =
    ``(16, 4096, 64, 128)``: both megasteps compile, and the lazy
    parameter pass takes the node table (8,192 rows, not 65,536)."""
    from repro.core.scheduler import execute, readout_roots
    from repro.core.structure import DeviceSchedule
    from repro.models.treelstm import TreeLSTMVertex
    from repro.obs.registry import fresh_registry
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    T, M, K, N, A, X = 16, 4096, 64, 128, 2, 300
    sd = lambda s, d=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        s, d, sharding=one_chip)
    i32 = jnp.int32
    sched = DeviceSchedule(
        child_ids=sd((T, M, A), i32), child_mask=sd((T, M, A)),
        ext_ids=sd((T, M), i32), node_mask=sd((T, M)),
        slot_of=sd((K, N), i32), node_valid=sd((K, N)),
        root_slots=sd((K,), i32), sort_perm=sd((T, M * A), i32),
        sorted_child_ids=sd((T, M * A), i32), run_head=sd((T, M * A), i32))
    fn = TreeLSTMVertex(input_dim=X, hidden=H, arity=A)
    params = jax.tree.map(lambda p: sd(p.shape, p.dtype),
                          jax.eval_shape(fn.init, jax.random.PRNGKey(0)))

    def loss(p, ext, dev, target):
        buf = execute(fn, p, dev, ext, fusion_mode="megastep").buf
        h = readout_roots(buf, dev)[:, -H:]
        return jnp.mean((h - target) ** 2)

    with fresh_registry() as reg:
        compiled = jax.jit(jax.grad(loss)).lower(
            params, sd((K * N + 1, X)), sched, sd((K, H))).compile()
    assert reg.counter("sched.lazy_pass", index="nodes") == 1
    assert reg.counter("sched.lazy_pass", index="slots") == 0
    assert sorted(_kernel_names(compiled)) == ["_bwd_megastep_kernel",
                                               "_megastep_kernel"]
