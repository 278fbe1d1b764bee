"""Shared benchmark utilities: timing, CSV emission, model setup.

All benchmarks print ``name,value,unit,detail`` CSV rows so
``benchmarks/run.py`` can aggregate them into bench_output.txt, and
keep structured records (value + optional mean/p50 stats) that run.py
serializes to per-suite ``results/BENCH_<suite>.json`` files — the
machine-readable perf trajectory.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np


def _time_loop(fn: Callable[[], Any], warmup: int, iters: int,
               min_time_s: float) -> List[float]:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    t_total = 0.0
    i = 0
    while i < iters or t_total < min_time_s:
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        dt = time.perf_counter() - t0
        times.append(dt)
        t_total += dt
        i += 1
        if i > 100:
            break
    return times


def time_fn(fn: Callable[[], Any], *, warmup: int = 2, iters: int = 5,
            min_time_s: float = 0.0) -> float:
    """Median wall seconds per call of a (jitted) thunk."""
    return float(np.median(_time_loop(fn, warmup, iters, min_time_s)))


def time_stats(fn: Callable[[], Any], *, warmup: int = 2, iters: int = 5,
               min_time_s: float = 0.0) -> Dict[str, float]:
    """Timing distribution of a thunk: ``p50_ms``, ``mean_ms``, ``iters``."""
    times = np.asarray(_time_loop(fn, warmup, iters, min_time_s))
    return {"p50_ms": float(np.median(times) * 1e3),
            "mean_ms": float(np.mean(times) * 1e3),
            "iters": int(times.size)}


def row(name: str, value: float, unit: str, detail: str = "") -> str:
    line = f"{name},{value:.6g},{unit},{detail}"
    print(line)
    return line


class Collector:
    """Accumulates benchmark rows both as printed CSV (legacy
    bench_output.txt path) and as structured records for BENCH_*.json."""

    def __init__(self):
        self.rows: List[str] = []
        self.records: List[Dict[str, Any]] = []

    def add(self, name: str, value: float, unit: str, detail: str = "",
            stats: Optional[Dict[str, float]] = None):
        self.rows.append(row(name, value, unit, detail))
        rec: Dict[str, Any] = {"name": name, "value": float(value),
                               "unit": unit, "detail": detail}
        if stats:
            rec.update(stats)
        self.records.append(rec)

    def add_time(self, name: str, stats: Dict[str, float], detail: str = ""):
        """Record a timing with its distribution (value = p50 ms)."""
        self.add(name, stats["p50_ms"], "ms", detail, stats=stats)


# ---------------------------------------------------------------------------
# Per-stage breakdown rows (obs.trace + obs.registry)
# ---------------------------------------------------------------------------

def emit_pipeline_stages(*, n_graphs: int = 12, batch_size: int = 4,
                         hidden: int = 32, input_dim: int = 32,
                         max_len: int = 12, seed: int = 0) -> None:
    """Drive one tiny compose → pack → fused fwd → fused bwd pass
    through :class:`~repro.pipeline.SchedulePipeline` so every pipeline
    stage span lands in the active registry's ``span.*`` histograms.

    No-op when no tracer is installed — suites stay zero-overhead when
    run standalone; ``benchmarks/run.py`` installs a per-suite tracer
    and calls this once per suite, so every ``BENCH_*.json`` carries
    the same stage-breakdown rows regardless of which paths the suite
    itself exercises.  The ``fwd``/``bwd`` spans time execution (the
    programs are compiled outside the spans, and each span waits for
    its result)."""
    from repro.obs import trace
    if trace.get_tracer() is None:
        return
    import jax.numpy as jnp

    from repro.configs.paper import get_paper_model
    from repro.core.scheduler import execute, readout_roots
    from repro.pipeline import SchedulePipeline

    m = get_paper_model("var_lstm")
    fn = m.make_vertex(hidden=hidden, input_dim=input_dim)
    params = fn.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)
    graphs = m.make_graphs(n_graphs, max_len=max_len, rng=rng)
    inputs = [rng.standard_normal((g.num_nodes, input_dim)
                                  ).astype(np.float32) for g in graphs]
    pipe = SchedulePipeline(ext_dim=input_dim)
    batches, _ = pipe.compose(graphs, inputs, batch_size=batch_size)
    for i, cb in enumerate(batches[:2]):
        pb = pipe.pack(*cb.as_item())
        dev, ext = pb.dev, pb.ext

        def _loss(p, e, dev=dev):
            r = execute(fn, p, dev, e, fusion_mode="megastep")
            return jnp.sum(readout_roots(r.buf, dev) ** 2)

        fwd = jax.jit(lambda p, e, dev=dev: execute(
            fn, p, dev, e, fusion_mode="megastep").buf)
        bwd = jax.jit(jax.grad(_loss))
        jax.block_until_ready(fwd(params, ext))   # compile outside spans
        jax.block_until_ready(bwd(params, ext))
        with trace.correlate(batch=i):
            with trace.span("fwd", batch=i):
                jax.block_until_ready(fwd(params, ext))
            with trace.span("bwd", batch=i):
                jax.block_until_ready(bwd(params, ext))


def add_stage_rows(col: Collector, registry=None) -> int:
    """Turn the active registry's ``span.*`` histograms into
    ``stage/<name>`` records (value = p50 ms, with mean/iters stats) so
    ``compare.py`` diffs the per-stage breakdown alongside the suite's
    own rows.  Returns the number of rows added."""
    from repro.obs.registry import get_registry
    reg = registry if registry is not None else get_registry()
    snap = reg.snapshot()
    added = 0
    for key in sorted(snap["histograms"]):
        if not key.startswith("span."):
            continue
        s = snap["histograms"][key]
        col.add_time(f"stage/{key[len('span.'):]}",
                     {"p50_ms": s["p50"], "mean_ms": s["mean"],
                      "iters": s["count"]},
                     detail=f"window={s['window']}")
        added += 1
    return added
