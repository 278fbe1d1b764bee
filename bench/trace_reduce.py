"""From a profiler trace to the numbers the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
:func:`reduce_dir` reads it with ``jax.profiler.ProfileData``.  What the
chip's trace holds (TPU v5e, jax 0.9):

  * a plane ``/device:TPU:<n>`` per chip; its line ``XLA Ops`` has one
    event per operation that ran, named by the HLO instruction's text
    (``%fusion.3 = f32[...] fusion(...), ...``).  Loops (``%while``)
    contain the operations of their bodies, so an operation's own time
    is its duration less that of the operations nested in it;
  * a plane ``/host:CPU`` whose line of the main thread (named after
    the interpreter: ``python``, ``python3``) holds the
    ``jax.profiler.TraceAnnotation`` spans the loops open
    (``bench.window`` around the traced window), on the same clock.

A Pallas kernel is a ``tpu_custom_call``; the trace does not give its
kernel function's name, so :func:`kernel_of` tells the megastep kernels
apart by their operands (see there).

:func:`reduce` keeps ``window_s`` (the ``bench.window`` span),
``busy_s`` (per chip the union of operation intervals inside the
window, averaged over the chips), ``ops`` (per operation its launches
and own seconds, averaged over the chips) and ``breakdown``: the ten
operations with the most own time, and the ten largest sums of idle
time by what the host's main thread was in at the middle of each gap:
the innermost of the loops' annotations and the program's spans
(``host_spans``).
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"

_ALIAS = re.compile(r"output_to_operand_aliasing=\{\{(\d*)\}: \((\d+),")


def find_trace(d: str) -> str:
    files = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {d}, found {files}")
    return files[0]


def kernel_of(op: str):
    """The fused megastep kernel an operation is, or ``None``.

    ``_megastep_kernel`` (forward; serving's frontier tick runs it too)
    writes one buffer in place: a single output aliased to operand 3,
    after its three scalar-prefetched operands, the third of which is
    the one-word level offset ``s32[1]``.  ``_bwd_megastep_kernel``
    returns a tuple (the gradient buffer, aliased, and its stash)."""
    if 'custom_call_target="tpu_custom_call"' not in op:
        return None
    lhs, _, args = op.partition(" custom-call(")
    out = lhs.split(" = ", 1)[-1]
    m = _ALIAS.search(op)
    if out.startswith("(") and m and m.group(1) == "0":
        return "_bwd_megastep_kernel"
    operands = args.split(", ")
    if (not out.startswith("(") and m and m.group(2) == "3"
            and len(operands) > 2 and operands[2].startswith("s32[1]")):
        return "_megastep_kernel"
    return None


def short_name(op: str) -> str:
    """``_megastep_kernel`` for a megastep, else the instruction's name
    and opcode: ``%fusion.3 = fusion``."""
    k = kernel_of(op)
    if k:
        return k
    lhs, _, rhs = op.partition(" = ")
    m = re.search(r"\}?\s([a-z][a-z0-9\-_.]*)\(", rhs)
    opcode = m.group(1) if m else rhs.split("(")[0][-40:]
    return f"{lhs.strip()} = {opcode}"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _own_times(events):
    """``[(name, own_seconds)]`` of properly nested ``(name, s, e)``."""
    out = []
    stack = []                       # [name, s, e, covered]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            n, s0, e0, cov = stack.pop()
            out.append((n, (e0 - s0 - cov) / 1e9))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    while stack:
        n, s0, e0, cov = stack.pop()
        out.append((n, (e0 - s0 - cov) / 1e9))
    return out


def reduce(pd, top: int = 10, host_spans=(), window_perf_ns=None) -> dict:
    """``host_spans``: the program's spans on the main thread as
    ``(name, start, end)`` in ``perf_counter`` nanoseconds, and
    ``window_perf_ns`` the ``perf_counter`` reading taken as the
    ``bench.window`` annotation opened: together they put the spans on
    the profiler's clock."""
    devices, main = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            # The main thread's line (named after the interpreter's
            # executable) is the one that holds the window's annotation.
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                if any(n == WINDOW for n, _s, _e in evs):
                    main = evs
    if not devices:
        raise RuntimeError("the trace holds no TPU operations")
    windows = [(s, e) for n, s, e in main if n == WINDOW]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        spans = [(s, e) for evs in devices.values() for _n, s, e in evs]
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)

    n_dev = len(devices)
    busy, ops, gaps = 0.0, {}, []
    for i, name in enumerate(sorted(devices)):
        evs = [(short_name(n), max(s, lo), min(e, hi))
               for n, s, e in devices[name] if e > lo and s < hi]
        merged = _union((s, e) for _n, s, e in evs)
        busy += sum(e - s for s, e in merged) / 1e9
        for n, own in _own_times(evs):
            c = ops.setdefault(n, [0, 0.0])
            c[0] += 1
            c[1] += own
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    ops = {n: [c / n_dev, t / n_dev] for n, (c, t) in ops.items()}

    labels = [(s, e, n) for n, s, e in main if n.startswith("bench.")]
    if windows and window_perf_ns is not None:
        shift = windows[0][0] - window_perf_ns
        labels += [(s + shift, e + shift, n) for n, s, e in host_spans]
    labels.sort(key=lambda x: (x[0], -x[1]))
    idle = {}
    for (s, e), label in zip(gaps, _innermost(labels,
                                              [(s + e) // 2 for s, e in gaps])):
        idle[label] = idle.get(label, 0.0) + (e - s) / 1e9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][1])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / n_dev,
            "devices": n_dev, "ops": ops,
            "breakdown": {
                "device_ops": [[n, t] for n, (_c, t) in top_ops],
                "idle_gaps": [[n, t] for n, t in
                              sorted(idle.items(), key=lambda kv: -kv[1])
                              [:top]]}}


def _innermost(intervals, times):
    """For each of ``times`` (ascending), the name of the innermost of
    the properly nested ``intervals`` (``(s, e, name)`` by start) open
    then."""
    out, stack, k = [], [], 0
    for t in times:
        while k < len(intervals) and intervals[k][0] <= t:
            while stack and stack[-1][1] <= intervals[k][0]:
                stack.pop()
            stack.append(intervals[k])
            k += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2] if stack else "no host event")
    return out


def reduce_dir(d: str, **kw) -> dict:
    import jax
    return reduce(jax.profiler.ProfileData.from_file(find_trace(d)), **kw)


def kernel_seconds(red: dict, kernel: str):
    """``(launches, own seconds)`` of ``kernel`` in the window, or
    ``None`` when the trace holds none of it."""
    hit = red["ops"].get(kernel)
    return None if hit is None else (hit[0], hit[1])
