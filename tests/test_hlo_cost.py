"""The trip-count-aware HLO cost walker — the §Roofline instrument —
validated against XLA's own cost analysis on loop-free programs and
against hand counts on scans/collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_cost
from tests.util_subproc import run_with_devices


def test_matches_xla_on_loop_free():
    d = 256
    w = jnp.ones((d, d), jnp.float32)

    def f(x):
        for _ in range(6):
            x = jnp.tanh(x @ w)
        return x

    c = jax.jit(f).lower(jnp.ones((8, d))).compile()
    ours = hlo_cost.analyze(c.as_text())
    xla = hlo_cost.xla_cost_dict(c)
    assert ours.flops == pytest.approx(xla["flops"], rel=0.02)
    assert ours.hbm_bytes == pytest.approx(xla["bytes accessed"], rel=0.02)


def test_scan_trip_multiplication():
    d, n = 128, 17
    w = jnp.ones((d, d), jnp.float32)

    def scanned(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=n)
        return y

    c = jax.jit(scanned).lower(jnp.ones((4, d))).compile()
    ours = hlo_cost.analyze(c.as_text())
    expected_dot = n * 2 * 4 * d * d
    assert ours.flops == pytest.approx(expected_dot, rel=0.05)
    # XLA's own number misses the ×n
    assert hlo_cost.xla_cost_dict(c)["flops"] < ours.flops / (n / 2)


def test_nested_scan():
    d = 64
    w = jnp.ones((d, d), jnp.float32)

    def inner(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ w, None), x, None, length=3)
        return y

    def outer(x):
        y, _ = jax.lax.scan(lambda c, _: (inner(c), None), x, None, length=5)
        return y

    c = jax.jit(outer).lower(jnp.ones((2, d))).compile()
    ours = hlo_cost.analyze(c.as_text())
    assert ours.flops == pytest.approx(15 * 2 * 2 * d * d, rel=0.05)


def test_shape_histogram_consistent():
    def f(x):
        return jnp.tanh(x @ jnp.ones((64, 64))) @ jnp.ones((64, 32))

    c = jax.jit(f).lower(jnp.ones((8, 64))).compile()
    ours = hlo_cost.analyze(c.as_text())
    assert sum(ours.by_shape.values()) == pytest.approx(ours.hbm_bytes)


def test_parse_shapes():
    from repro.analysis.hlo_cost import parse_shapes
    s = parse_shapes("(s32[], /*index=1*/bf16[8,256]{1,0}, f32[2,2])")
    assert [x.dtype for x in s] == ["s32", "bf16", "f32"]
    assert s[1].bytes == 8 * 256 * 2
    assert s[0].dims == ()


@pytest.mark.slow
def test_collectives_in_loops():
    run_with_devices("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.analysis import hlo_cost
mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("d",))
w = jnp.ones((256, 256), jnp.float32)
def f(x, w):
    def step(c, _): return jnp.tanh(c @ w), None
    y, _ = jax.lax.scan(step, x, None, length=5)
    return jnp.sum(y)
with mesh:
    c = jax.jit(jax.grad(f, argnums=1),
                in_shardings=(NamedSharding(mesh, P("d")), NamedSharding(mesh, P())),
                out_shardings=NamedSharding(mesh, P())).lower(
                    jnp.ones((64, 256)), w).compile()
a = hlo_cost.analyze(c.as_text())
assert abs(a.collectives.get("all-reduce", 0) - 5*256*256*4) < 1e-6, a.collectives
print("OK")
""", n_devices=8)


def test_roofline_terms_and_bottleneck():
    from repro.analysis.roofline import RooflineReport, V5E
    rep = RooflineReport(
        arch="x", shape="train_4k", mesh="m", chips=256,
        hlo_flops=1e14, hlo_bytes=1e12, collective_bytes=1e11,
        collective_detail={}, model_flops_total=1e16,
        peak_memory_bytes=1e9, device_kind="TPU v5 lite")
    assert rep.t_compute == pytest.approx(1e14 / V5E.peak_flops)
    assert rep.t_memory == pytest.approx(1e12 / V5E.hbm_bw)
    assert rep.t_collective == pytest.approx(1e11 / V5E.ici_bw)
    assert rep.bottleneck == "collective"
    assert 0 < rep.mfu_bound <= 1.0 or rep.mfu_bound > 0


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the table by device kind; an unknown kind is an
    error, never a silent v5e default."""
    from repro.analysis.roofline import PEAKS, peaks_for
    assert peaks_for("TPU v5 lite") is PEAKS["TPU v5 lite"]
    assert peaks_for("TPU v5 lite").peak_flops == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("cpu")


def test_model_flops_moe_active():
    from repro.analysis.roofline import model_flops
    assert model_flops(100, 10) == 6000
    assert model_flops(100, 10, active_param_count=25) == 1500
