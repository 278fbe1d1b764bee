"""Continuous-batching serving example (deliverable b, serving side).

The Cavs property at inference time: ONE compiled decode program over a
fixed slot pool; dynamic request arrival/retirement is pure data.  This
mirrors the paper's Var-LSTM batching — variable-length work batched
without recompilation.

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.configs.archs import reduced
from repro.models.transformer import TransformerLM
from repro.serve import Request, ServeEngine

enable_compile_cache()

cfg = reduced(get_config("granite-3-8b"))
lm = TransformerLM(cfg)
params = lm.init(jax.random.PRNGKey(0))

engine = ServeEngine(lm, params, num_slots=4, max_len=64)
rng = np.random.default_rng(0)

# staggered arrivals: some requests only arrive after serving started
first_wave = [Request(request_id=i,
                      prompt=rng.integers(0, cfg.vocab, size=int(n)),
                      max_new_tokens=8)
              for i, n in enumerate(rng.integers(3, 12, size=6))]
second_wave = [Request(request_id=10 + i,
                       prompt=rng.integers(0, cfg.vocab, size=5),
                       max_new_tokens=6)
               for i in range(3)]

for r in first_wave:
    engine.submit(r)
t0 = time.perf_counter()
for _ in range(4):                      # engine is already decoding...
    engine.step()
for r in second_wave:                   # ...when more requests arrive
    engine.submit(r)
finished = engine.run()
dt = time.perf_counter() - t0

tokens = sum(len(r.output) for r in finished)
print(f"served {len(finished)} requests / {tokens} tokens in "
      f"{engine.ticks} ticks ({dt:.2f}s wall, {tokens/dt:.1f} tok/s)")
print(f"slot pool: {engine.num_slots} slots; requests were admitted and "
      f"retired continuously — no recompilation at any point")
for r in sorted(finished, key=lambda r: r.request_id)[:4]:
    print(f"  req {r.request_id}: prompt[{len(r.prompt)}] → {r.output}")
