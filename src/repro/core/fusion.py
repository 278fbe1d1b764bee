"""Kernel-fusion evidence + eager/lazy operator classification (Cavs §3.5).

The paper runs a fusion detector over the dataflow graph of ``F`` and
generates fused elementwise kernels.  Under XLA, elementwise-chain fusion
is performed by the compiler; what this module provides is

  1. the *verification* surface: count kernels (HLO fusions / loops) in
     the compiled program of ``F`` so tests can check the kernel
     -launch reduction that Fig. 10 attributes to fusion, and
  2. the static *eager/lazy classification* of Proposition 2: given a
     closed-over jaxpr of ``F``, identify which equations depend on
     ``gather`` output (must run inside the sequential region) and which
     feed only ``scatter``-independent outputs (may be deferred).

The classification is used by the scheduler indirectly: ``F`` declares
its eager prefix via ``project_inputs`` (hoisted, §scheduler) and its
lazy suffix is realized by post-scan readouts plus lazy-batched parameter
gradients.  ``classify_jaxpr`` exists so tests can check a vertex
function's declared split against the derived one.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, FrozenSet, List, Set, Tuple

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Eager/lazy classification over the jaxpr of F (Proposition 2)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OperatorClasses:
    """Indices of equations in ``jaxpr.eqns`` per class.

    ``eager``: depend on no gathered input (can be hoisted / streamed).
    ``lazy``: nothing on the gather→scatter path depends on them (can be
    deferred past all batching tasks).
    ``chain``: everything on the gather→scatter data path.
    """

    eager: Tuple[int, ...]
    lazy: Tuple[int, ...]
    chain: Tuple[int, ...]


def classify_jaxpr(fn: Callable, gather_argnums: Tuple[int, ...],
                   scatter_outnums: Tuple[int, ...],
                   *example_args) -> OperatorClasses:
    """Classify the equations of ``jax.make_jaxpr(fn)`` per Cavs Prop. 2.

    ``gather_argnums``: positions of arguments that carry gathered child
    state; ``scatter_outnums``: positions of outputs that are scattered.
    """
    jaxpr = jax.make_jaxpr(fn)(*example_args).jaxpr

    gather_vars: Set[Any] = set()
    for i in gather_argnums:
        gather_vars.add(jaxpr.invars[i])

    # Forward reachability from gather.
    depends_on_gather: List[bool] = []
    tainted: Set[Any] = set(gather_vars)
    for eqn in jaxpr.eqns:
        hit = any((v in tainted) for v in eqn.invars
                  if not isinstance(v, jex_core.Literal))
        depends_on_gather.append(hit)
        if hit:
            tainted.update(eqn.outvars)

    # Backward reachability to scatter.
    scatter_vars: Set[Any] = {jaxpr.outvars[i] for i in scatter_outnums
                              if not isinstance(jaxpr.outvars[i],
                                                jex_core.Literal)}
    feeds_scatter = [False] * len(jaxpr.eqns)
    needed: Set[Any] = set(scatter_vars)
    for i in range(len(jaxpr.eqns) - 1, -1, -1):
        eqn = jaxpr.eqns[i]
        if any(v in needed for v in eqn.outvars):
            feeds_scatter[i] = True
            needed.update(v for v in eqn.invars
                          if not isinstance(v, jex_core.Literal))

    eager, lazy, chain = [], [], []
    for i in range(len(jaxpr.eqns)):
        if not depends_on_gather[i]:
            eager.append(i)          # Prop. 2: no gather ancestor
        elif not feeds_scatter[i]:
            lazy.append(i)           # Prop. 2: not on any gather→scatter path
        else:
            chain.append(i)
    return OperatorClasses(tuple(eager), tuple(lazy), tuple(chain))


# ---------------------------------------------------------------------------
# Fusion evidence from compiled HLO
# ---------------------------------------------------------------------------

_KERNELISH = ("fusion", "custom-call", "dot", "convolution", "scatter",
              "gather", "dynamic-update-slice", "dynamic-slice", "reduce",
              "while", "all-reduce", "all-gather", "reduce-scatter",
              "all-to-all", "collective-permute")


def count_hlo_kernels(compiled_text: str) -> Dict[str, int]:
    """Histogram of kernel-launch-like ops in optimized HLO text.

    The TPU/GPU analogue of the paper's "number of kernel launches":
    each top-level fusion / dot / custom-call is one launch.  Used by the
    fusion ablation benchmark to show the op-count drop.
    """
    counts: Dict[str, int] = {}
    for line in compiled_text.splitlines():
        s = line.strip()
        if "=" not in s or s.startswith(("HloModule", "ENTRY", "//", "%param")):
            continue
        rhs = s.split("=", 1)[1].strip()
        # "f32[...]{...} op-name(" — op name is the first token after types.
        for tok in rhs.split():
            t = tok.split("(")[0]
            if not t:
                continue
            base = t.rstrip(".0123456789")
            if base in _KERNELISH:
                counts[base] = counts.get(base, 0) + 1
                break
            if not (t.startswith(("f32", "f16", "bf16", "s32", "u32", "s8",
                                  "u8", "pred", "s64", "u64", "f64", "s16",
                                  "u16", "c64", "tuple", "token", "(", "/")
                    ) or t[0].isdigit()):
                counts.setdefault("other", 0)
                counts["other"] += 1
                break
    return counts


def compiled_kernel_count(fun: Callable, *args, **jit_kwargs) -> int:
    """Total kernel-ish ops of ``jit(fun)`` on example args."""
    compiled = jax.jit(fun, **jit_kwargs).lower(*args).compile()
    counts = count_hlo_kernels(compiled.as_text())
    return sum(v for k, v in counts.items() if k != "other")
