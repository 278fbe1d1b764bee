"""Serving loop for token chains: ``HybridChainEngine.submit/step``
(the continuous engine with a state pool and a key/value pool) under
an open loop of arrivals at a fixed rate.

Each request is one chat turn scored token by token: a prompt and an
answer of lengths drawn from the traffic file's lognormals (the same
multiset of lengths in every run; the seed orders them and draws the
token ids), the answer teacher-forced.  The engine returns the logits
at the turn's last token.

Set-up: the weights from the seed on the device, the engine, and its
warm-up, which compiles every program the window can dispatch (one
window program for every window length, the logits for each
retirement-count bucket), then two short requests end to end.

Window: as ``serve.py``: one thread submits each request when it is
due and steps the engine while there is work; every request due in the
window is followed to its terminal state, a minute past the close at
most; latency runs from the time a request was due to the engine's
stamp of its end.

A ``--trace 1`` run measures the same window untraced, for the
counters (``serve.state_row_occupancy``: the engine's live state rows
over the pool's, summed over the window's ticks), then keeps the
arrivals coming for two more phases of ``TRACE_SECONDS`` each and as
long again after them: a profiler trace alone (the device's idle share,
the breakdown, and the ``ssm_step`` kernel's time, with the lanes the
engine fired meanwhile), then the program's span tracer alone.  The
spans never sync the device; the span phase starts on whatever backlog
the profile's write-out left.

Check: a seeded sample of ``check_requests`` requests that ended ``ok``,
spread over the range of lengths, has its logits compared with the
plain reference's, run on the same weights in blocks of
``check_block`` requests padded to the longest turn
(``check.logit_rms_gap``; the worst request's gap is printed).
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import check
import device
import generate
from tracing import SpanWindow, TracedWindow, freeze_heap

#: Length of each traced phase after a ``--trace 1`` window, in seconds.
TRACE_SECONDS = 3.0
#: How long past the window's close the loop waits for answers.
GRACE_SECONDS = 60.0


def _request(rid, tokens):
    from repro.core.structure import InputGraph
    from repro.serve import ContinuousRequest
    return ContinuousRequest(
        request_id=rid, graph=InputGraph(children=generate.chain(len(tokens))),
        inputs=tokens.astype(np.float32)[:, None])


def turns(tr: dict, count: int, vocab: int, rng: np.random.Generator):
    """``count`` token sequences: prompt and answer lengths from the
    traffic file (each multiset fixed, ordered by ``rng``), ids uniform
    over the vocabulary."""
    lens = (rng.permutation(generate.sizes(tr["prompt"], count))
            + rng.permutation(generate.sizes(tr["answer"], count)))
    block = rng.integers(0, vocab, int(lens.sum()), dtype=np.int64)
    ends = np.cumsum(lens)
    return [block[e - n: e] for n, e in zip(lens, ends)]


def sample(lengths, ok, count: int, rng: np.random.Generator):
    """Up to ``count`` indices of requests that ended ``ok``, one from
    each of ``count`` equal strata of their lengths (so the sample
    spans the range), each drawn from ``rng``."""
    idx = np.nonzero(ok)[0]
    idx = idx[np.argsort(lengths[idx], kind="stable")]
    if len(idx) <= count:
        return [int(i) for i in idx]
    edges = np.linspace(0, len(idx), count + 1).astype(int)
    return [int(idx[rng.integers(a, b)]) for a, b in zip(edges, edges[1:])]


def reference_logits(ref, params, cfg, seqs, block: int, max_len: int,
                     precision: str):
    """The reference's last-token logits of ``seqs``, ``block`` at a
    time, each block padded to ``max_len`` (one program for all)."""
    f = jax.jit(lambda p, t, last: ref.last_logits(p, cfg, t, last,
                                                   precision=precision))
    out = []
    for b in range(0, len(seqs), block):
        part = seqs[b: b + block]
        toks = np.zeros((block, max_len), np.int32)
        last = np.zeros(block, np.int32)
        for i, s in enumerate(part):
            toks[i, :len(s)] = s
            last[i] = len(s) - 1
        out.extend(np.asarray(f(params, jnp.asarray(toks),
                                jnp.asarray(last)))[:len(part)])
    return out


def _values(prog, ref) -> dict:
    if not prog:
        return {"logit_rms_gap": np.inf, "logit_gap_worst": np.inf}
    return {"logit_rms_gap": check.root_rms_gap(prog, ref),
            "logit_gap_worst": max(check.root_rms_gap([a], [b])
                                   for a, b in zip(prog, ref))}


def run(ctx) -> dict:
    from repro.models.granite_hybrid import GraniteHybridVertex, HybridDims
    from repro.serve import HybridChainEngine

    cfg, tr = ctx.cfg, ctx.traffic
    rng = np.random.default_rng(ctx.seed)
    extra = 3 * TRACE_SECONDS if ctx.trace else 0.0
    arrivals = generate.poisson_arrivals(tr["rate_per_s"],
                                         ctx.seconds + extra, rng)
    seqs = turns(tr, len(arrivals), cfg["vocab_size"], rng)
    lengths = np.array([len(s) for s in seqs], np.int64)
    reqs = [_request(i, s) for i, s in enumerate(seqs)]

    key = jax.random.PRNGKey(generate.seed_key(ctx.seed))
    params = jax.jit(lambda k: ctx.reference.init(k, cfg))(key)
    fn = GraniteHybridVertex(HybridDims.from_hf(cfg), **cfg["vertex_args"])
    eng_cfg = tr["engine"]
    engine = HybridChainEngine(
        fn, params, num_rows=eng_cfg["num_rows"],
        kv_tokens=eng_cfg["kv_tokens"],
        frontier_width=eng_cfg["frontier_width"], clock=time.perf_counter)
    with jax.profiler.TraceAnnotation("bench.warmup"):
        engine.warm()
        for rid, n in ((-1, 20), (-2, 37)):
            engine.submit(_request(rid, rng.integers(0, cfg["vocab_size"],
                                                     n)))
        engine.run()
    bad = [r for r in engine.finished if r.status != "ok"]
    if bad:
        raise RuntimeError(f"warm-up request {bad[0].request_id} ended "
                           f"{bad[0].status}: {bad[0].error}")
    n_warm = len(engine.finished)

    freeze_heap()
    compiles0 = ctx.compiles.count
    ticks0 = engine.ticks
    live0, total0 = engine.state_rows_live, engine.state_rows_total
    sent = np.full(len(reqs), np.nan)
    i, n = 0, len(reqs)
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    due = t0 + arrivals
    close = t0 + ctx.seconds
    give_up = close + extra + GRACE_SECONDS
    at_close = None
    profiled = spanned = None
    prof_lanes = span_ticks = span_end = None
    while True:
        now = time.perf_counter()
        while i < n and due[i] <= now:
            engine.submit(reqs[i])
            sent[i] = time.perf_counter()
            i += 1
        if at_close is None and now >= close:
            at_close = (engine.ticks, engine.state_rows_live,
                        engine.state_rows_total)
            if ctx.trace:
                profiled = TracedWindow(ctx.trace_dir(), spans=False)
                prof_lanes = (engine.lanes_fired, engine.chain_starts,
                              engine.ticks)
        if profiled and not spanned and now >= close + TRACE_SECONDS:
            profiled.stop()
            prof_lanes = tuple(b - a for a, b in zip(
                prof_lanes, (engine.lanes_fired, engine.chain_starts,
                             engine.ticks)))
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
    if at_close is None:
        at_close = (engine.ticks, engine.state_rows_live,
                    engine.state_rows_total)
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
    mine = due < close
    lat_ms = (np.where(ok, fin, give_up) - due)[mine] * 1e3
    in_window = ok & (fin <= close)
    late_ms = (sent - due)[mine] * 1e3
    p50, p95 = np.percentile(lat_ms, [50, 95])
    degradations = engine.health()["degradations"]
    d = fn.dims
    rec = {"kind": cfg["kind"], "window_s": ctx.seconds,
           "frontier_width": engine.frontier_width,
           "answered": int(in_window.sum()),
           "answered_tokens": int(lengths[in_window].sum()),
           "ticks": int(at_close[0] - ticks0),
           "state_rows_live": int(at_close[1] - live0),
           "state_rows_total": int(at_close[2] - total0),
           "device_kind": jax.devices()[0].device_kind,
           "ssm": {"layers": len(d.mamba_layers), "heads": d.ssm_heads,
                   "head_dim": d.ssm_head_dim, "d_state": d.d_state,
                   "d_conv": d.d_conv}}
    if profiled:
        rec.update(profiled.record())
        rec["profile_lanes"], rec["profile_starts"], rec["profile_ticks"] = \
            (int(x) for x in prof_lanes)
    if spanned:
        rec["spans"] = spanned.spans()
        rec["span_ticks"] = int(span_ticks)

    pick = sample(lengths, ok, tr["check_requests"], rng)
    prog = [reqs[j].logits for j in pick]
    for r in reqs:
        r.logits = None
    del engine
    gc.collect()
    max_len = cfg["vertex_args"]["max_len"]
    chosen = [seqs[j] for j in pick]
    ref = reference_logits(ctx.reference, params, cfg, chosen,
                           tr["check_block"], max_len, "highest")
    values = _values(prog, ref)
    control = None
    if ctx.control:
        ctl = reference_logits(ctx.reference, params, cfg, chosen,
                               tr["check_block"], max_len,
                               ctx.control_how()[1])
        control = _values(ctl, ref)

    notes = [f"serve requests={int(mine.sum())} ok={int(ok.sum())} "
             f"answered_in_window={int(in_window.sum())} "
             f"backlog_at_close={int(np.sum(mine & ~in_window))} "
             f"ticks={rec['ticks']} warm_requests={n_warm} "
             f"compiles_in_window={compiles} degradations={degradations} "
             f"checked={len(pick)} checked_tokens="
             f"{int(lengths[pick].min()) if pick else 0}.."
             f"{int(lengths[pick].max()) if pick else 0} "
             f"logit_gap_worst={values['logit_gap_worst']!r}",
             f"serve p95_ms={float(p95)!r} "
             f"generator_late_p95_ms={float(np.nanpercentile(late_ms, 95))!r} "
             f"late_max_ms={float(np.nanmax(late_ms))!r} "
             f"offered_per_s={int(mine.sum()) / ctx.seconds!r} "
             f"tokens_per_s={rec['answered_tokens'] / ctx.seconds!r}"]
    if profiled:
        notes.append(f"serve traced profiled_s={rec['traced_s']!r} "
                     f"profile_ticks={rec['profile_ticks']} "
                     f"profile_lanes={rec['profile_lanes']} "
                     f"span_ticks={span_ticks}")
    return {"metrics": {"serve_p50_ms": p50, "serve_p95_ms": p95,
                        "serve_req_per_s": in_window.sum() / ctx.seconds,
                        "setup_s": setup_s},
            "values": values, "sound": degradations == 0 and bool(ok.all()),
            "attempted": n, "failed": int((~ok).sum()),
            "memory_peak_bytes": memory_peak, "record": rec,
            "notes": notes, "control_values": control}
