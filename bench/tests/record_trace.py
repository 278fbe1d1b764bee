#!/usr/bin/env python3
"""Record the small chip trace ``test_trace_reduce.py`` reads.

    python bench/tests/record_trace.py [OUT]   # on a TPU, from the checkout root

Two Tree-LSTM training steps at the cell's widths on a batch of 16
trees, through the trainer and the fused megasteps, traced as a run's
window is (``tracing.TracedWindow``), written to
``OUT.xplane.pb`` (by default ``bench/tests/data/tree_lstm_2steps``)
with the counts the test expects beside it in ``OUT.json``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import generate  # noqa: E402
import trace_reduce  # noqa: E402
from reference import treelstm  # noqa: E402
from tracing import TracedWindow  # noqa: E402

OUT = os.path.join(HERE, "data", "tree_lstm_2steps")


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def main(argv) -> int:
    out = argv[0] if argv else OUT
    from repro.core.scheduler import execute, readout_roots
    from repro.core.structure import InputGraph
    from repro.pipeline import BucketPolicy, SchedulePipeline
    from repro.train import MetricLogger, Trainer, TrainConfig

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    cfg = _load("configs", "tree_lstm_h512")
    w = cfg["vertex_args"]
    H, X = w["hidden"], w["input_dim"]
    rng = np.random.default_rng(7)
    spec = _load("traffic", "sst_train")["structure"]
    structs = generate.structures(spec, 16, rng)
    xs = generate.inputs([len(s) for s in structs], X, rng, 0.5)
    ys = generate.targets(16, H, rng, 0.5)
    from repro.models.treelstm import TreeLSTMVertex
    vertex = TreeLSTMVertex(**w)
    params = treelstm.init(jax.random.PRNGKey(7), cfg)

    def loss_fn(p, b):
        buf = execute(vertex, p, b["dev"], b["ext"],
                      fusion_mode="megastep").buf
        h = readout_roots(buf, b["dev"])[:, -H:]
        return jnp.mean((h - b["target"]) ** 2), {}

    pipe = SchedulePipeline(ext_dim=X,
                            bucket_policy=BucketPolicy(mode="pow2"))
    pb = pipe.pack([InputGraph(children=s) for s in structs], xs)
    batch = {"dev": pb.dev, "ext": pb.ext, "target": ys}
    trainer = Trainer(loss_fn, lambda _k: params,
                      TrainConfig(lr=1e-3, warmup_steps=1, log_every=1))
    state = trainer.init_state(jax.random.PRNGKey(0))
    quiet = MetricLogger(log_fn=lambda *_: None)
    state, _ = trainer.fit(state, iter([batch] * 3), steps=1, logger=quiet)
    tmp = os.path.join(BENCH, "out", "record_trace")
    traced = TracedWindow(tmp)
    state, _ = trainer.fit(state, iter([batch] * 3), steps=3, logger=quiet)
    traced.stop()
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(trace_reduce.find_trace(tmp), out + ".xplane.pb")
    red = traced.record()["trace"]
    levels = int(pb.dev.T)
    with open(out + ".json", "w") as f:
        json.dump({"steps": 2, "levels": levels,
                   "window_s": red["window_s"], "busy_s": red["busy_s"],
                   "megastep": red["ops"].get("_megastep_kernel"),
                   "bwd_megastep": red["ops"].get("_bwd_megastep_kernel")},
                  f, indent=1)
    print(json.dumps({k: red[k] for k in ("window_s", "busy_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
