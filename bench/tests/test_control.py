"""The control: the plain reference computed in the precision below the
one the configurations state (float32 at ``highest``; the control's
products take three bfloat16 passes, ``high``), put in the program's
place.  At the cells' own widths, on fewer structures than a batch so
that the CPU holds it, the check has to judge it not correct."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import generate
from reference import graph, lstm, treelstm

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = [("tree_lstm_h512", treelstm, "sst_train"),
         ("var_lstm_h512", lstm, "ptb_train")]
K = 8


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _how(cfg):
    return jnp.dtype(cfg["control"]["dtype"]).type, cfg["control"]["precision"]


def _norms(tree):
    return {k: float(jnp.linalg.norm(v)) for k, v in tree.items()}


@pytest.mark.parametrize("config,mod,traffic", CELLS)
def test_training_control_fails(config, mod, traffic):
    cfg, tr = _load("configs", config), _load("traffic", traffic)
    rng = np.random.default_rng(20)
    widths = cfg["vertex_args"]
    structs = generate.structures(tr["structure"], 3 * K, rng)
    xs = generate.inputs([len(s) for s in structs], widths["input_dim"], rng,
                         tr["input_scale"])
    ys = generate.targets(3 * K, widths["hidden"], rng, tr["target_scale"])
    p = mod.init(jax.random.PRNGKey(generate.seed_key(20)), cfg)
    batches = []
    for b in range(3):
        pl = graph.plan(structs[b * K:(b + 1) * K],
                        widths.get("arity", 1))
        batches.append((pl, graph.block_inputs(xs[b * K:(b + 1) * K], pl),
                        ys[b * K:(b + 1) * K]))
    ref = graph.train_steps(mod, p, batches, tr["optimizer"])
    ctl = graph.train_steps(mod, p, batches, tr["optimizer"],
                            how=_how(cfg))
    values = check.train_gaps(ctl["losses"], _norms(ctl["grad1"]),
                              _norms(ctl["delta"]),
                              {"losses": ref["losses"],
                               "grad1": _norms(ref["grad1"]),
                               "delta": _norms(ref["delta"])})
    limits = {k: v for k, v in cfg["limits"]["train"].items() if k in values}
    assert not all(ok for *_x, ok in check.judge(values, limits)), values


def test_serving_control_fails():
    cfg, tr = _load("configs", "tree_lstm_h512"), _load("traffic",
                                                         "sst_poisson")
    rng = np.random.default_rng(21)
    widths = cfg["vertex_args"]
    structs = generate.structures(tr["structure"], 2 * K, rng)
    xs = generate.inputs([len(s) for s in structs], widths["input_dim"], rng,
                         tr["input_scale"])
    p = treelstm.init(jax.random.PRNGKey(3), cfg)
    pl = graph.plan(structs, widths["arity"])
    x = graph.block_inputs(xs, pl)
    ref = np.asarray(graph.root_states(treelstm, p, pl, x))
    ctl = np.asarray(graph.root_states(treelstm, p, pl, x, how=_how(cfg)))
    values = {"root_rms_gap": check.root_rms_gap(ctl, ref)}
    assert not check.judge(values, cfg["limits"]["serve"])[0][3], values
