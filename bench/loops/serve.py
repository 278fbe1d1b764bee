"""Serving loop: ``ContinuousBatchEngine.submit/step`` under an open
loop of arrivals at a fixed rate.

Set-up: the weights from the seed on the device, the engine, and a
warm-up that compiles every program the window can use: one request
alone for each dispatch-window length (a left-deep tree of ``k``
leaves runs ``k`` levels), and one tree of each size the traffic can
send (the engine projects inputs at a power-of-two row count).  The
requests of the window are drawn from the seed beforehand.

Check: every request due in the window that ended ``ok`` has its root
state compared with the plain reference's (``check.root_gap``).

Window: one thread submits each request when it is due (or as soon
after as the engine's step lets it) and steps the engine while there
is work.  Every request due in the window is followed to its terminal
state, a minute past the close at most.  Latency runs from the time a
request was due to the engine's stamp of its terminal state.

A ``--trace 1`` run measures the same window, untraced, for the
counters (``serve.lane_occupancy``), and then keeps the arrivals
coming for two more phases of ``TRACE_SECONDS`` each, and as long
again after them: a profiler trace alone (the device's idle share and
the breakdown), then the program's span tracer alone (host time per
tick and per admission).  The span tracer syncs with the
device inside the engine's spans, which slows the engine until a queue
builds: the span metrics describe that regime, and nothing else is
read under it.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

import check
import device
import generate
from reference import graph as refgraph
from tracing import SpanWindow, TracedWindow, freeze_heap as _freeze_heap

#: Length of each traced phase after a ``--trace 1`` window, in seconds.
TRACE_SECONDS = 3.0
#: How long past the window's close the loop waits for answers.
GRACE_SECONDS = 60.0


def _request(cls, rid, struct, x):
    from repro.core.structure import InputGraph
    return cls(request_id=rid, graph=InputGraph(children=struct), inputs=x)


def _warm(engine, cls, x_dim, tr, rng):
    """Run every window length and every input bucket once."""
    spec = tr["structure"]
    scale = tr["input_scale"]
    rid = -1
    for leaves in range(1, engine.policy.max_window + 1):
        s = generate.caterpillar_tree(leaves)
        engine.submit(_request(cls, rid, s, generate.inputs(
            [len(s)], x_dim, rng, scale)[0]))
        rid -= 1
        engine.run()
    structs = [generate.random_binary_tree(n, rng)
               for n in range(spec["min"], spec["max"] + 1)]
    for s, x in zip(structs, generate.inputs([len(s) for s in structs],
                                             x_dim, rng, scale)):
        engine.submit(_request(cls, rid, s, x))
        rid -= 1
    engine.run()
    bad = [r for r in engine.finished if r.status != "ok"]
    if bad:
        raise RuntimeError(f"warm-up request {bad[0].request_id} ended "
                           f"{bad[0].status}: {bad[0].error}")


def _root_values(prog, ref) -> dict:
    if not prog:
        return {"root_gap": np.inf, "root_rms_gap": np.inf}
    return {"root_gap": check.root_gap(prog, ref),
            "root_rms_gap": check.root_rms_gap(prog, ref)}


def run(ctx) -> dict:
    from repro.serve import ContinuousBatchEngine, ContinuousRequest

    cfg, tr = ctx.cfg, ctx.traffic
    widths = cfg["vertex_args"]
    rng = np.random.default_rng(ctx.seed)
    extra = 3 * TRACE_SECONDS if ctx.trace else 0.0
    arrivals = generate.poisson_arrivals(tr["rate_per_s"],
                                         ctx.seconds + extra, rng)
    structs = generate.corpus(tr, len(arrivals), rng)
    sizes = np.array([len(s) for s in structs], np.int64)
    xs = generate.inputs(sizes, widths["input_dim"], rng, tr["input_scale"])
    reqs = [_request(ContinuousRequest, i, s, x)
            for i, (s, x) in enumerate(zip(structs, xs))]

    key = jax.random.PRNGKey(generate.seed_key(ctx.seed))
    params = jax.jit(lambda k: ctx.reference.init(k, cfg))(key)
    params_host = jax.tree.map(np.asarray, params)
    eng_cfg = tr["engine"]
    engine = ContinuousBatchEngine(
        ctx.vertex(), params, num_rows=eng_cfg["num_rows"],
        frontier_width=eng_cfg["frontier_width"], fusion_mode="megastep",
        clock=time.perf_counter)
    with jax.profiler.TraceAnnotation("bench.warmup"):
        _warm(engine, ContinuousRequest, widths["input_dim"], tr, rng)
    n_warm = len(engine.finished)

    _freeze_heap()
    compiles0 = ctx.compiles.count
    ticks0 = engine.ticks
    sent = np.full(len(reqs), np.nan)
    i, n = 0, len(reqs)
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    due = t0 + arrivals
    close = t0 + ctx.seconds
    give_up = close + extra + GRACE_SECONDS
    ticks_at_close = None
    profiled = spanned = None
    span_ticks = span_end = None
    while True:
        now = time.perf_counter()
        while i < n and due[i] <= now:
            engine.submit(reqs[i])
            sent[i] = time.perf_counter()
            i += 1
        if ticks_at_close is None and now >= close:
            ticks_at_close = engine.ticks
            if ctx.trace:
                profiled = TracedWindow(ctx.trace_dir(), spans=False)
        if profiled and not spanned and now >= close + TRACE_SECONDS:
            profiled.stop()
            # Writing the trace out can take seconds: the span phase
            # starts once it is written, and the arrivals go on past it.
            spanned, span_ticks = SpanWindow(), engine.ticks
            span_end = time.perf_counter() + TRACE_SECONDS
        if spanned and not spanned.stopped and now >= span_end:
            spanned.stop()
            span_ticks = engine.ticks - span_ticks
        if i == n and engine.num_active == 0 and not engine.queue:
            break
        if now >= give_up:
            break
        if engine.num_active or engine.queue:
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                engine.step()
        elif i < n:
            time.sleep(max(0.0, min(due[i] - time.perf_counter(), 0.01)))
    if ticks_at_close is None:
        ticks_at_close = engine.ticks
    if profiled:
        profiled.stop()
    if spanned and not spanned.stopped:
        spanned.stop()
        span_ticks = engine.ticks - span_ticks
    compiles = ctx.compiles.count - compiles0
    gc.unfreeze()
    memory_peak = device.peak_bytes(ctx.cell["chips"])

    ok = np.array([r.status == "ok" for r in reqs])
    fin = np.array([getattr(r, "_finished_at", np.nan) for r in reqs])
    # The window's requests; a traced run sends more after it.
    mine = due < close
    # A request that never ended ok misses every limit: it counts at
    # the longest wait the loop allowed.
    lat_ms = (np.where(ok, fin, give_up) - due)[mine] * 1e3
    in_window = ok & (fin <= close)
    late_ms = (sent - due)[mine] * 1e3
    p50, p95 = np.percentile(lat_ms, [50, 95])
    backlog = int(np.sum(mine & ~in_window))
    degradations = engine.health()["degradations"]

    rec = {"kind": cfg["kind"], "window_s": ctx.seconds,
           "frontier_width": engine.frontier_width,
           "answered": int(in_window.sum()),
           "answered_vertices": int(sizes[in_window].sum()),
           "ticks": int(ticks_at_close - ticks0)}
    if profiled:
        rec.update(profiled.record())
    if spanned:
        rec["spans"] = spanned.spans()
        rec["span_ticks"] = int(span_ticks)

    # The reference checks every answer of the window.
    pick = [int(j) for j in np.nonzero(ok)[0]]
    prog = [reqs[j].root_state for j in pick]
    del engine
    gc.collect()
    ref = []
    chunk = tr["check_chunk"]
    arity = widths.get("arity", 1)
    for c in range(0, len(pick), chunk):
        part = pick[c: c + chunk]
        pl = refgraph.plan([structs[j] for j in part], arity)
        x = refgraph.block_inputs([xs[j] for j in part], pl)
        ref.extend(np.asarray(refgraph.root_states(ctx.reference,
                                                   params_host, pl, x)))
    values = _root_values(prog, ref)
    control = None
    if ctx.control:
        ctl = []
        for c in range(0, len(pick), chunk):
            part = pick[c: c + chunk]
            pl = refgraph.plan([structs[j] for j in part], arity)
            x = refgraph.block_inputs([xs[j] for j in part], pl)
            ctl.extend(np.asarray(refgraph.root_states(
                ctx.reference, params_host, pl, x, how=ctx.control_how())))
        control = _root_values(ctl, ref)

    notes = [f"serve requests={int(mine.sum())} ok={int(ok.sum())} "
             f"answered_in_window={int(in_window.sum())} "
             f"backlog_at_close={backlog} ticks={rec['ticks']} "
             f"warm_requests={n_warm} compiles_in_window={compiles} "
             f"degradations={degradations} checked={len(pick)}",
             f"serve p95_ms={float(p95)!r} "
             f"generator_late_p95_ms={float(np.nanpercentile(late_ms, 95))!r} "
             f"late_max_ms={float(np.nanmax(late_ms))!r} "
             f"offered_per_s={int(mine.sum()) / ctx.seconds!r}"]
    if spanned:
        notes.append(f"serve traced profiled_s={rec['traced_s']!r} "
                     f"span_ticks={span_ticks} spans={len(rec['spans'])}")
    return {"metrics": {"serve_p50_ms": p50, "serve_p95_ms": p95,
                        "serve_req_per_s": in_window.sum() / ctx.seconds,
                        "setup_s": setup_s},
            "values": values, "sound": degradations == 0 and bool(ok.all()),
            "attempted": n, "failed": int((~ok).sum()),
            "memory_peak_bytes": memory_peak, "record": rec,
            "notes": notes, "control_values": control}
