"""End-to-end LM training driver (deliverable b): train a ~100M-param
transformer for a few hundred steps on CPU with the full production
stack — data pipeline, AdamW + schedule, grad accumulation, async
checkpointing, metric logging, auto-resume.

Run:  PYTHONPATH=src python examples/train_lm.py \
          [--steps 300] [--ckpt /tmp/lm_ckpt]
"""

import argparse
import dataclasses

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.archs import reduced
from repro.data import lm_batches, synthetic_corpus
from repro.models.transformer import TransformerLM
from repro.pipeline import AsyncPacker
from repro.train import MetricLogger, TrainConfig, Trainer


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--n-micro", type=int, default=2)
    args = ap.parse_args()

    # ~100M params: widen the reduced granite config
    cfg = dataclasses.replace(
        reduced(get_config("granite-3-8b")),
        name="granite-100m", num_layers=8, d_model=512, n_heads=8,
        n_kv_heads=4, d_ff=1536, vocab=8192, head_dim=64)
    lm = TransformerLM(cfg)
    n_params = cfg.param_count()
    print(f"arch={cfg.name}: {n_params/1e6:.1f}M params, "
          f"batch={args.batch}×{args.seq} tokens, "
          f"n_micro={args.n_micro}")

    trainer = Trainer(
        lambda p, b: lm.loss(p, b), lm.init,
        TrainConfig(lr=1e-3, warmup_steps=30, total_steps=args.steps,
                    n_micro=args.n_micro, ckpt_dir=args.ckpt,
                    ckpt_every=100, log_every=20))
    state = trainer.init_state(jax.random.PRNGKey(0))
    state, start = trainer.maybe_restore(state)
    if start:
        print(f"resumed from checkpoint at step {start}")

    corpus = synthetic_corpus(3_000_000, cfg.vocab, seed=0)
    # The schedule pipeline's async packing stage doubles as a device
    # stager for plain token batches: host batch assembly + transfer
    # overlap with the previous step's compute (Trainer.fit closes the
    # background producer when the loop exits).
    batches = AsyncPacker(
        lm_batches(corpus, args.batch, args.seq, seed=0),
        lambda b: {k: jax.device_put(np.asarray(v)) for k, v in b.items()},
        depth=2)
    logger = MetricLogger(tokens_per_step=args.batch * args.seq)
    state, logger = trainer.fit(state, batches, steps=args.steps,
                                logger=logger)
    first = next(r for r in logger.history if "loss" in r)
    last = logger.history[-1]
    print(f"loss {first['loss']:.3f} → {last['loss']:.3f} over "
          f"{int(np.asarray(state.step))} steps "
          f"({last.get('tokens_per_sec', 0):.0f} tok/s)")
    assert last["loss"] < first["loss"], "training must make progress"


if __name__ == "__main__":
    main()
