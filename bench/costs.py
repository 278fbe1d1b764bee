"""Operations and bytes that the work of real vertices requires, from
the configuration's widths and the structures' shapes alone.

Counted: the multiply-adds of matrix products, as 2 operations each
(gate nonlinearities and sums add about ``20 H`` a vertex against
millions, and are left out).  Never counted: pad slots, pad levels,
and work a path recomputes.  Bytes are float32 (4 a value), each
operand moved once: a kernel launch reads its weights once, a vertex's
pulled input row, its children's states, and writes its state.

Per level, ``n_v`` real vertices, ``n_e`` real child edges, ``n_i``
vertices with at least one child.  Widths: ``H`` hidden, ``X`` input,
``S = 2H`` state (``[c | h]`` for both kinds), ``G = 4H`` gate lanes.

Tree-LSTM (child-sum): per edge one ``[H] @ [H, H]`` forget-gate
product, per vertex with children three more over the children's sum
of ``h``.  LSTM: per edge one ``[H] @ [H, 4H]``.  Both: per vertex the
hoisted input projection ``[X] @ [X, 4H]``.
"""

from __future__ import annotations

from generate import levels as struct_levels

F32 = 4


def level_counts(structs):
    """``[(n_v, n_e, n_i)]`` per level over ``structs`` laid side by
    side (level ``t`` of every structure runs in one launch)."""
    n_v, n_e, n_i = {}, {}, {}
    for s in structs:
        lv = struct_levels(s)
        for v, ch in enumerate(s):
            t = int(lv[v])
            n_v[t] = n_v.get(t, 0) + 1
            n_e[t] = n_e.get(t, 0) + len(ch)
            n_i[t] = n_i.get(t, 0) + (1 if ch else 0)
    return [(n_v[t], n_e[t], n_i.get(t, 0)) for t in sorted(n_v)]


def recurrent_flops(kind: str, H: int, n_e: int, n_i: int) -> float:
    if kind == "treelstm":
        return 2.0 * H * H * n_e + 3 * 2.0 * H * H * n_i
    if kind == "lstm":
        return 2.0 * H * 4 * H * n_e
    raise ValueError(f"no operation count for kind {kind!r}")


def projection_flops(X: int, H: int, n_v: int) -> float:
    return 2.0 * X * 4 * H * n_v


def weight_bytes(kind: str, H: int) -> float:
    """The recurrent weights and the bias a launch holds."""
    if kind == "treelstm":
        return F32 * (4 * H * H + 4 * H)
    if kind == "lstm":
        return F32 * (H * 4 * H + 4 * H)
    raise ValueError(f"no byte count for kind {kind!r}")


def fwd_kernel(kind: str, H: int, n_v: int, n_e: int, n_i: int):
    """One forward megastep over one level: ``(flops, bytes)``.  Reads
    the weights, each child's state and each vertex's pulled gate row;
    writes each vertex's state."""
    S, G = 2 * H, 4 * H
    flops = recurrent_flops(kind, H, n_e, n_i)
    nbytes = weight_bytes(kind, H) + F32 * (n_e * S + n_v * (G + S))
    return flops, nbytes


def bwd_kernel(kind: str, H: int, n_v: int, n_e: int, n_i: int):
    """One backward megastep over one level: ``(flops, bytes)``.  The
    products that carry the state's cotangent to the children (as many
    operations as the forward's recurrent products; the parameter
    gradients are a separate pass); reads the weights, each vertex's
    cotangent and gate row, each child's state, and adds into each
    child's cotangent (a read and a write)."""
    S, G = 2 * H, 4 * H
    flops = recurrent_flops(kind, H, n_e, n_i)
    nbytes = weight_bytes(kind, H) + F32 * (n_v * (S + G) + n_e * 3 * S)
    return flops, nbytes


def train_flops(kind: str, X: int, H: int, counts) -> float:
    """Forward and backward operations one training step requires:
    the projection forward and its weight gradient, the recurrent
    products forward, to the children and to the weights."""
    total = 0.0
    for n_v, n_e, n_i in counts:
        total += 2 * projection_flops(X, H, n_v) \
            + 3 * recurrent_flops(kind, H, n_e, n_i)
    return total


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and what bounds it."""
    t_c = flops / peaks["flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def kernel_least_seconds(kernel, kind: str, H: int, steps, peaks: dict):
    """Summed least time of ``kernel`` (:func:`fwd_kernel` or
    :func:`bwd_kernel`) over every real level of ``steps`` (each a
    :func:`level_counts` list), and how many launches each bound
    governs."""
    total, bound = 0.0, {"compute": 0, "memory": 0}
    for counts in steps:
        for n_v, n_e, n_i in counts:
            t, b = least_seconds(*kernel(kind, H, n_v, n_e, n_i), peaks)
            total += t
            bound[b] += 1
    return total, bound
