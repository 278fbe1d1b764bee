"""Distribution layer: spec resolution, param rules (head boundaries,
EP), pipeline parallelism (subprocess, 4 devices), compression, elastic
planning."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist import compress, elastic, fault
from repro.dist.pipeline import bubble_fraction
from tests.util_subproc import run_with_devices


# ---------------------------------------------------------------------------
# Spec resolution (pure logic — fake mesh via namespace)
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_resolve_spec_divisibility():
    from repro.dist.sharding import resolve_spec
    mesh = FakeMesh({"data": 16, "model": 16})
    # divisible → sharded; non-divisible → dropped
    assert resolve_spec(mesh, (32, 64), ("data", "model")) == \
        P("data", "model")
    assert resolve_spec(mesh, (8, 64), ("data", "model")) == \
        P(None, "model")
    # tuple axes multiply
    mesh2 = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert resolve_spec(mesh2, (64, 4), (("pod", "data"), None)) == \
        P(("pod", "data"), None)
    assert resolve_spec(mesh2, (17, 4), (("pod", "data"), None)) == P(None, None)


def test_param_specs_head_boundaries():
    """GQA kv weights with n_kv < model axis must REPLICATE, not split
    within heads (the involuntary-remat fix)."""
    from repro.dist.sharding import ShardingPolicy, param_specs
    mesh = FakeMesh({"data": 16, "model": 16})
    pol = ShardingPolicy(fsdp=False)
    params = {
        "attn": {"wq": jnp.zeros((512, 32, 128)),   # 32 q heads / 16 ✓
                 "wk": jnp.zeros((512, 8, 128)),    # 8 kv heads / 16 ✗
                 "wo": jnp.zeros((32, 128, 512))},
        "mlp": {"w_gate": jnp.zeros((512, 2048)),
                "w_down": jnp.zeros((2048, 512))},
    }
    specs = param_specs(params, mesh, pol)
    assert specs["attn"]["wq"] == P(None, "model", None)
    assert specs["attn"]["wk"] == P(None, None, None)      # replicated!
    assert specs["attn"]["wo"] == P("model", None, None)
    assert specs["mlp"]["w_gate"] == P(None, "model")
    assert specs["mlp"]["w_down"] == P("model", None)


def test_param_specs_moe_ep_vs_tp():
    from repro.dist.sharding import ShardingPolicy, param_specs
    mesh = FakeMesh({"data": 16, "model": 16})
    # stacked repeats axis + experts: [R, E, D, F]
    params = {"moe": {"w_gate": jnp.zeros((7, 64, 512, 1024)),
                      "w_down": jnp.zeros((7, 64, 1024, 512))}}
    ep = param_specs(params, mesh, ShardingPolicy(expert_axis="experts"))
    assert ep["moe"]["w_gate"] == P(None, "model", None, None)
    assert ep["moe"]["w_down"] == P(None, "model", None, None)
    tp = param_specs(params, mesh, ShardingPolicy(expert_axis="ff"))
    assert tp["moe"]["w_gate"] == P(None, None, None, "model")
    assert tp["moe"]["w_down"] == P(None, None, "model", None)
    # shared experts are dense
    shared = {"moe": {"shared": {"w_gate": jnp.zeros((512, 1024))}}}
    sp = param_specs(shared, mesh, ShardingPolicy())
    assert sp["moe"]["shared"]["w_gate"] == P(None, "model")


def test_param_specs_fsdp():
    from repro.dist.sharding import ShardingPolicy, param_specs
    mesh = FakeMesh({"data": 16, "model": 16})
    pol = ShardingPolicy(fsdp=True)
    params = {"embed": jnp.zeros((51200, 4096)),
              "attn": {"wq": jnp.zeros((4096, 32, 128))}}
    specs = param_specs(params, mesh, pol)
    assert specs["embed"] == P("model", "data")
    assert specs["attn"]["wq"] == P("data", "model", None)


def test_cache_specs():
    from repro.dist.sharding import ShardingPolicy, cache_specs
    mesh = FakeMesh({"data": 16, "model": 16})
    pol = ShardingPolicy()
    cache = {
        "prologue": [{"attn": {"k": jnp.zeros((128, 8, 4096, 128)),
                               "v": jnp.zeros((128, 8, 4096, 128))}}],
        "pattern": [{"mla": {"c": jnp.zeros((7, 128, 4096, 512)),
                             "kr": jnp.zeros((7, 128, 4096, 64))},
                     "mamba": {"ssm": jnp.zeros((7, 128, 32, 64, 128)),
                               "conv": jnp.zeros((7, 128, 3, 256))}}],
    }
    specs = cache_specs(cache, mesh, pol)
    # kv heads 8 < 16 → seq sharded instead (GQA fallback)
    assert specs["prologue"][0]["attn"]["k"] == P("data", None, "model", None)
    # stacked leaves get a leading None
    assert specs["pattern"][0]["mla"]["c"] == P(None, "data", "model", None)
    assert specs["pattern"][0]["mamba"]["ssm"] == \
        P(None, "data", "model", None, None)
    # conv: tiny seq dim 3 not divisible → dropped
    assert specs["pattern"][0]["mamba"]["conv"] == P(None, "data", None, None)


def test_logical_spec_dedupes_axes():
    from repro.models.layers import axis_rules, logical_spec
    rules = {"batch": "data", "heads": "model", "seq": "model"}
    with axis_rules(rules):
        assert logical_spec(("batch", "heads", "seq", None)) == \
            P("data", "model", None, None)
        assert logical_spec(("batch", "seq", None)) == \
            P("data", "model", None)


# ---------------------------------------------------------------------------
# Pipeline (subprocess: needs 4 devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_gpipe_matches_serial_and_is_differentiable():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.dist import pipeline
devs = np.asarray(jax.devices()).reshape(4)
mesh = Mesh(devs, ("pod",))
def stage_fn(p, x): return jnp.tanh(x @ p["w"] + p["b"])
n, d, m, mb = 4, 8, 6, 2
stacked = {"w": jax.random.normal(jax.random.PRNGKey(0), (n, d, d)) * 0.5,
           "b": jnp.zeros((n, d))}
xs = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
f = pipeline.gpipe_spmd(stage_fn, mesh)
with mesh:
    out = f(stacked, xs)
ref = xs
for i in range(n):
    ref = stage_fn({"w": stacked["w"][i], "b": stacked["b"][i]}, ref)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
floss = pipeline.gpipe_spmd(stage_fn, mesh, loss_fn=lambda a: jnp.sum(a**2))
def serial_loss(s, xs):
    h = xs
    for i in range(n): h = stage_fn(jax.tree.map(lambda p: p[i], s), h)
    return jnp.sum(h**2)
with mesh:
    l1 = float(floss(stacked, xs))
    g1 = jax.grad(lambda s: floss(s, xs))(stacked)
np.testing.assert_allclose(l1, float(serial_loss(stacked, xs)), rtol=1e-5)
g2 = jax.grad(serial_loss)(stacked, xs)
jax.tree.map(lambda a, b: np.testing.assert_allclose(
    np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g1, g2)
print("PIPE_OK")
""", n_devices=4)


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
    assert bubble_fraction(1, 8) == 0.0


# ---------------------------------------------------------------------------
# Compression
# ---------------------------------------------------------------------------

def test_quantization_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    xq = compress.fake_quant(x)
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(x - xq))) <= amax / 127.0 * 0.51


def test_error_feedback_reduces_bias():
    """Across steps, EF-compressed gradient sums converge to the true
    sum (the EF guarantee) while naive compression accumulates bias."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(256) * 1e-4)  # tiny → harsh quant
    steps = 50
    ef = compress.ErrorFeedback.init({"g": g})
    acc_ef = np.zeros(256)
    acc_naive = np.zeros(256)
    for _ in range(steps):
        out, ef = ef.apply({"g": g})
        acc_ef += np.asarray(out["g"])
        acc_naive += np.asarray(compress.fake_quant(g))
    true = steps * np.asarray(g)
    err_ef = np.linalg.norm(acc_ef - true)
    err_naive = np.linalg.norm(acc_naive - true)
    assert err_ef < err_naive * 0.5


@pytest.mark.slow
def test_cross_pod_mean_int8():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.dist.compress import cross_pod_mean_int8
mesh = Mesh(np.asarray(jax.devices()).reshape(2), ("pod",))
x = jnp.stack([jnp.full((8,), 1.0), jnp.full((8,), 3.0)])
f = jax.shard_map(lambda v: cross_pod_mean_int8(v[0], axis_name="pod")[None],
                  mesh=mesh, in_specs=P("pod"), out_specs=P("pod"))
with mesh:
    out = f(x)
np.testing.assert_allclose(np.asarray(out), 2.0, rtol=0.02)
print("OK")
""", n_devices=2)


def test_cross_pod_mean_counts_replicas_exactly_in_low_precision():
    """bf16 has an 8-bit mantissa: 257 replicas counted via
    ``psum(ones)`` in the payload dtype round to 256 and the mean
    divides by the wrong count.  The count (and accumulation) must run
    in f32 regardless of payload dtype."""
    n = 257
    xs = jnp.full((n,), 127.0, jnp.bfloat16)  # fake_quant-exact payload
    out = jax.vmap(lambda x: compress.cross_pod_mean_int8(
        x, axis_name="pod"), axis_name="pod")(xs)
    assert out.dtype == jnp.bfloat16
    # 127.0 quantizes exactly (scale 1.0, q=127) and 257*127 = 32639 is
    # exact in f32, so the mean must come back as exactly 127.0.
    np.testing.assert_array_equal(np.asarray(out, np.float32), 127.0)


def test_cross_pod_mean_ef_residual_and_convergence():
    """The collective EF form: residuals stay local, and the sum of
    emitted means converges to the true mean sum."""
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.standard_normal((2, 64)) * 1e-4, jnp.float32)
    steps = 50
    res = jnp.zeros_like(vals)
    acc = np.zeros(64)

    @jax.jit
    def one(v, r):
        return jax.vmap(lambda x, e: compress.cross_pod_mean_int8_ef(
            x, e, axis_name="pod"), axis_name="pod")(v, r)

    for _ in range(steps):
        mean, res = one(vals, res)
        np.testing.assert_array_equal(np.asarray(mean[0]),
                                      np.asarray(mean[1]))
        acc += np.asarray(mean[0])
    true = steps * np.asarray(jnp.mean(vals, axis=0))
    naive = steps * np.asarray(jax.vmap(
        lambda x: compress.cross_pod_mean_int8(x, axis_name="pod"),
        axis_name="pod")(vals))[0]
    err_ef = np.linalg.norm(acc - true)
    err_naive = np.linalg.norm(naive - true)
    assert err_ef < err_naive * 0.5


def test_ef_apply_matches_error_feedback_class():
    rng = np.random.default_rng(2)
    tree = {"a": jnp.asarray(rng.standard_normal(32), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)}
    res = jax.tree.map(jnp.zeros_like, tree)
    ef = compress.ErrorFeedback.init(tree)
    for _ in range(3):
        out_fn, res = compress.ef_apply(tree, res)
        out_cls, ef = ef.apply(tree)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), out_fn, out_cls)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), res, ef.residual)


# ---------------------------------------------------------------------------
# Elastic
# ---------------------------------------------------------------------------

def test_plan_downsize():
    plan = elastic.plan_downsize({"data": 16, "model": 16},
                                 dead_fraction=0.3)
    assert plan.new_shape["model"] == 16          # TP degree preserved
    assert plan.new_shape["data"] == 8            # pow2 below 11.2
    assert plan.dropped_rows == 8


def test_plan_downsize_counts_devices_as_integers():
    """Fractions that leave exactly a power of two must keep it: the
    old float path computed ``80 * (1 - 0.9) = 7.999…`` and halved the
    mesh to 4 although exactly 8 devices survive."""
    plan = elastic.plan_downsize({"data": 80}, dead_fraction=0.9)
    assert plan.new_shape["data"] == 8
    assert plan.dropped_rows == 72
    # 14 * 3/7 dead = 6 → 8 survive exactly (another fp-noise boundary)
    plan = elastic.plan_downsize({"data": 14, "model": 4},
                                 dead_fraction=3 / 7)
    assert plan.new_shape["data"] == 8
    assert plan.new_shape["model"] == 4


def test_plan_downsize_boundaries():
    # nothing dead → identity
    plan = elastic.plan_downsize({"data": 8}, dead_fraction=0.0)
    assert plan.new_shape["data"] == 8 and plan.dropped_rows == 0
    # everything dead → error, as does a nonsense fraction
    with pytest.raises(ValueError):
        elastic.plan_downsize({"data": 8}, dead_fraction=1.0)
    with pytest.raises(ValueError):
        elastic.plan_downsize({"data": 8}, dead_fraction=1.5)
    # one survivor is a legal (degenerate) mesh
    plan = elastic.plan_downsize({"data": 8}, dead_fraction=7 / 8)
    assert plan.new_shape["data"] == 1


def test_remesh_requires_enough_devices():
    with pytest.raises(ValueError):
        elastic.remesh(jax.devices(), {"data": 64, "model": 64})
