"""Encoder-decoder via push/pull composition (paper §3.1: "declare
multiple vertex functions ... and connect them appropriately").

Two (F, G) structures: an encoder LSTM over the source chain and a
decoder LSTM over the target chain.  The decoder PULLS the encoder's
final state (the cross-structure external data path) — in this
framework the pull is realized by feeding the encoder's root state into
the decoder's external-input rows.

Run:  PYTHONPATH=src python examples/encoder_decoder.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.scheduler import execute, readout_nodes, readout_roots
from repro.core.structure import chain, pack_batch, pack_external
from repro.models.rnn import LSTMVertex

enable_compile_cache()

B, SRC_LEN, TGT_LEN, D, H = 4, 10, 7, 16, 24
rng = np.random.default_rng(0)

enc = LSTMVertex(input_dim=D, hidden=H)
# decoder pulls [token_embedding | encoder_context] at every step
dec = LSTMVertex(input_dim=D + 2 * H, hidden=H)
params = {"enc": enc.init(jax.random.PRNGKey(0)),
          "dec": dec.init(jax.random.PRNGKey(1))}

# --- encoder structure: source chains ------------------------------------
src_graphs = [chain(SRC_LEN) for _ in range(B)]
src_inputs = [rng.standard_normal((SRC_LEN, D)).astype(np.float32) * 0.1
              for _ in range(B)]
enc_sched = pack_batch(src_graphs)
enc_ext = jnp.asarray(pack_external(src_inputs, enc_sched, D))
enc_dev = enc_sched.to_device()

# --- decoder structure: target chains -------------------------------------
tgt_graphs = [chain(TGT_LEN) for _ in range(B)]
tgt_tokens = [rng.standard_normal((TGT_LEN, D)).astype(np.float32) * 0.1
              for _ in range(B)]
dec_sched = pack_batch(tgt_graphs)
dec_dev = dec_sched.to_device()


# 1. schedule F_enc over the source chains; the root state is the
#    encoder's PUSH — the value made visible outside (F_enc, G_src).
# 2. pack decoder pulls: concat token embeds with the pushed context.
enc_buf = execute(enc, params["enc"], enc_dev, enc_ext).buf
context = np.asarray(readout_roots(enc_buf, enc_dev))   # [B, 2H]
dec_inputs = [np.concatenate(
    [tgt_tokens[k], np.repeat(context[k][None], TGT_LEN, 0)], axis=1)
    for k in range(B)]
dec_ext = jnp.asarray(pack_external(dec_inputs, dec_sched, D + 2 * H))


@jax.jit
def decode(params, dec_ext):
    buf = execute(dec, params["dec"], dec_dev, dec_ext).buf
    return readout_nodes(buf, dec_dev)[:, :, H:]        # [B, T, H]

outs = decode(params, dec_ext)
print(f"encoder chains {SRC_LEN} steps → context [B, {2*H}]")
print(f"decoder chains {TGT_LEN} steps pulling context → outputs "
      f"{outs.shape}")
assert np.all(np.isfinite(np.asarray(outs)))
print("enc-dec composition OK (two F's, push/pull connected)")
