"""The one traffic generator: structures, inputs, targets and arrival
times, all drawn from ``--seed`` and the parameters of a traffic file.

Structures are child lists (``children[v]`` = the vertices ``v`` reads
from); every generator emits children before parents, so a vertex's id
is above those of its children.  The tree and chain generators are
copies of the program's paper corpus generators (``configs/paper.py``
``_tree_lstm_graphs`` / ``_var_lstm_graphs`` over
``core/structure.py`` ``random_binary_tree`` / ``chain``), kept here
so that no change to the program can move the traffic.  They differ
in that the sizes are a distribution's quantiles (:func:`sizes`) and
the corpus is drawn from a fixed seed of the traffic file
(:func:`corpus`): the same work in every run, in the run's own order.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np

# ---------------------------------------------------------------------------
# Structures
# ---------------------------------------------------------------------------


def random_binary_tree(num_leaves: int, rng: np.random.Generator):
    """A random binary bracketing over ``num_leaves`` leaves (SST-like):
    repeatedly join two neighbouring subtrees picked at random."""
    children = [[] for _ in range(num_leaves)]
    frontier = list(range(num_leaves))
    while len(frontier) > 1:
        i = int(rng.integers(0, len(frontier) - 1))
        children.append([frontier[i], frontier[i + 1]])
        frontier[i: i + 2] = [len(children) - 1]
    return children


def caterpillar_tree(num_leaves: int):
    """A left-deep binary tree: ``num_leaves`` levels, the deepest
    binary tree over that many leaves."""
    children = [[] for _ in range(num_leaves)]
    top = 0
    for leaf in range(1, num_leaves):
        children.append([top, leaf])
        top = len(children) - 1
    return children


def chain(n: int):
    """A sequence: vertex ``t`` reads from ``t - 1``."""
    return [[] if t == 0 else [t - 1] for t in range(n)]


def sizes(spec: dict, count: int) -> np.ndarray:
    """The multiset of sizes (leaves of a tree, vertices of a chain)
    that a traffic file's ``structure`` entry gives ``count``
    structures: the quantiles at ``(i + 0.5) / count`` of a lognormal
    ``exp(mu + sigma * z)``, rounded down and clipped to ``[min, max]``.
    Every seed gets the same sizes, so that a seed changes the order
    and values of the work but not how much of it there is."""
    q = (np.arange(count) + 0.5) / count
    z = np.array([NormalDist().inv_cdf(x) for x in q])
    n = np.exp(spec["mu"] + spec["sigma"] * z).astype(np.int64)
    return np.clip(n, spec["min"], spec["max"])


def structures(spec: dict, count: int, rng: np.random.Generator):
    """``count`` structures of :func:`sizes`, in an order drawn from
    ``rng``; each tree's bracketing is drawn from ``rng`` as well."""
    order = rng.permutation(sizes(spec, count))
    if spec["shape"] == "random_binary_tree":
        return [random_binary_tree(int(n), rng) for n in order]
    if spec["shape"] == "chain":
        return [chain(int(n)) for n in order]
    raise ValueError(f"unknown structure shape {spec['shape']!r}")


def corpus(traffic: dict, count: int, rng: np.random.Generator):
    """``count`` structures of a traffic file, in an order drawn from
    ``rng``.  The structures themselves, tree bracketings included, are
    drawn from the file's ``corpus_seed``: like a treebank, the corpus
    is the same in every run, and the run's seed shuffles it."""
    fixed = structures(traffic["structure"], count,
                       np.random.default_rng(traffic["corpus_seed"]))
    return [fixed[i] for i in rng.permutation(count)]


def levels(children) -> np.ndarray:
    """Level of each vertex: 0 for a vertex that reads from none, else
    one above its highest child."""
    lvl = np.zeros(len(children), np.int64)
    for v, ch in enumerate(children):
        if ch:
            if max(ch) >= v:
                raise ValueError("children must come before parents")
            lvl[v] = 1 + max(lvl[c] for c in ch)
    return lvl


def root(children) -> int:
    """The one vertex no other reads from (the last one emitted)."""
    return len(children) - 1


# ---------------------------------------------------------------------------
# Inputs, targets, arrivals
# ---------------------------------------------------------------------------


def inputs(sizes, dim: int, rng: np.random.Generator, scale: float):
    """Per-structure input rows ``[size, dim]``: views into one block
    drawn at once (drawing per structure costs seconds at corpus
    size)."""
    sizes = np.asarray(sizes, np.int64)
    block = rng.standard_normal((int(sizes.sum()), dim), dtype=np.float32)
    block *= np.float32(scale)
    ends = np.cumsum(sizes)
    return [block[e - n: e] for n, e in zip(sizes, ends)]


def targets(count: int, dim: int, rng: np.random.Generator, scale: float):
    return rng.standard_normal((count, dim), dtype=np.float32) \
        * np.float32(scale)


def poisson_arrivals(rate: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets in ``[0, seconds)`` of an open loop of ``rate``
    requests a second: ``round(rate * seconds)`` gaps, the quantiles at
    ``(i + 0.5) / n`` of the exponential distribution of a Poisson
    process's gaps, scaled to fill the window and put in an order drawn
    from ``rng``.  Every run sends the same number of requests with the
    same set of gaps, so that a seed changes when the bursts come but
    not how much work the window holds."""
    n = int(round(rate * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))[:-1]])


def seed_key(seed: int) -> int:
    """A 31-bit key for ``jax.random.PRNGKey`` from any whole seed."""
    return int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1))
