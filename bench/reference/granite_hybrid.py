"""Granite-4.0-H (Hugging Face ``GraniteMoeHybrid`` with no experts),
forward over whole token sequences, in plain ``jax.numpy`` float32.

A decoder layer is ``h += r * mixer(rmsnorm(h))`` then
``h += r * mlp(rmsnorm(h))`` with ``r`` the residual multiplier.  The
mixer is a Mamba-2 layer or, where ``layer_types`` says ``attention``,
causal softmax attention with no positional encoding: ``q·k`` times
``attention_multiplier``, each key/value head shared by a group of
query heads.  The MLP is SwiGLU (``input_linear`` split into gate then
up).  Embeddings are scaled by ``embedding_multiplier``; logits are the
final-norm hidden state times the tied embedding, over
``logits_scaling``.

Mamba-2, per layer: ``in_proj`` → ``z | xBC | dt``; a causal depthwise
conv of width ``K`` with bias, then SiLU, over ``xBC``; ``x | B | C``;
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head the exact
sequential recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t ⊗ B_t``,
``y_t = h_t · C_t + D x_t``; then ``rmsnorm(y * silu(z))`` (one group,
eps ``rms_norm_eps``) and ``out_proj``.

Departures from Hugging Face's module: the first ``num_hidden_layers``
entries of ``layer_types`` are run (a period of the published 40);
dropout and caching are left out (inference, whole sequences); the
weights are the seeded random ones of :func:`init`, not a checkpoint;
``mamba_chunk_size`` does not apply (the recurrence is run token by
token, which is what the chunked form computes).  Attention runs in
query blocks of ``q_block`` rows, each a full softmax over its keys.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _dims(cfg: dict) -> dict:
    nl = cfg["num_hidden_layers"]
    return {"D": cfg["hidden_size"], "V": cfg["vocab_size"],
            "types": list(cfg["layer_types"][:nl]),
            "H": cfg["mamba_n_heads"], "P": cfg["mamba_d_head"],
            "N": cfg["mamba_d_state"], "K": cfg["mamba_d_conv"],
            "Hq": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"],
            "F": cfg["shared_intermediate_size"],
            "eps": cfg["rms_norm_eps"]}


def init(key, cfg: dict) -> dict:
    """Matrices and the embedding ``N(0, 0.02²)``, norms 1,
    ``A_log = log(linspace(1, 16, H))``, ``dt`` log-uniform in
    ``[1e-3, 1e-1]`` through its bias (the inverse softplus), ``D = 1``,
    conv weight and bias ``U(±1/√K)``."""
    d = _dims(cfg)
    D, H, P, N, K, F = d["D"], d["H"], d["P"], d["N"], d["K"], d["F"]
    Din = H * P
    C = Din + 2 * N
    kv = d["Hkv"] * (D // d["Hq"])
    keys = iter(jax.random.split(key, 1 + 8 * len(d["types"])))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    layers = []
    for kind in d["types"]:
        lp = {"norm1": jnp.ones((D,)), "norm2": jnp.ones((D,)),
              "w_mlp_in": normal((D, 2 * F)), "w_mlp_out": normal((F, D))}
        if kind == "mamba":
            b = 1.0 / math.sqrt(K)
            dt = jnp.exp(uniform((H,), math.log(1e-3), math.log(1e-1)))
            lp.update(w_in=normal((D, 2 * Din + 2 * N + H)),
                      conv_w=uniform((K, C), -b, b),
                      conv_b=uniform((C,), -b, b),
                      dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
                      A_log=jnp.log(jnp.linspace(1.0, 16.0, H)),
                      D=jnp.ones((H,)), gnorm=jnp.ones((Din,)),
                      w_out=normal((Din, D)))
        else:
            lp.update(wq=normal((D, D)), wk=normal((D, kv)),
                      wv=normal((D, kv)), wo=normal((D, D)))
        layers.append(lp)
    return {"embed": normal((d["V"], D)), "final_norm": jnp.ones((D,)),
            "layers": layers}


def _split(x):
    """``x`` as a high and a low bfloat16 part (the high part by
    masking the low 16 bits, which no compiler folds away)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), x.dtype)
    return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)


def make_einsum(precision: str):
    """Products at ``precision``: ``"highest"`` (float32) or ``"high"``
    (three bfloat16 passes, the control)."""
    if precision == "highest":
        return lambda spec, a, b: jnp.einsum(
            spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        def es(spec, a, b):
            (ah, al), (bh, bl) = _split(a), _split(b)
            e = lambda u, v: jnp.einsum(spec, u, v,
                                        preferred_element_type=jnp.float32)
            return e(ah, bh) + e(ah, bl) + e(al, bh)
        return es
    raise ValueError(f"unknown precision {precision!r}")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mamba(lp, x, d, es):
    B, L, _ = x.shape
    H, P, N, K = d["H"], d["P"], d["N"], d["K"]
    Din = H * P
    zxbcdt = es("bld,de->ble", x, lp["w_in"])
    z, xbc, dt = (zxbcdt[..., :Din], zxbcdt[..., Din:2 * Din + 2 * N],
                  zxbcdt[..., 2 * Din + 2 * N:])
    pad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(pad[:, k:k + L] * lp["conv_w"][k] for k in range(K))
                      + lp["conv_b"])
    xs = xbc[..., :Din].reshape(B, L, H, P)
    Bm, Cm = xbc[..., Din:Din + N], xbc[..., Din + N:]
    dt = jax.nn.softplus(dt + lp["dt_bias"])                  # [B, L, H]
    A = -jnp.exp(lp["A_log"])

    def step(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t * A)[:, :, None, None] * s
             + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        return s, jnp.sum(s * c_t[:, None, None, :], axis=-1)

    s0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, s0, (jnp.moveaxis(xs, 1, 0),
                                    jnp.moveaxis(dt, 1, 0),
                                    jnp.moveaxis(Bm, 1, 0),
                                    jnp.moveaxis(Cm, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1) + lp["D"][:, None] * xs
    y = y.reshape(B, L, Din) * jax.nn.silu(z)
    y = _rms(y, lp["gnorm"], d["eps"])
    return es("ble,ed->bld", y, lp["w_out"])


def _attention(lp, x, d, es, scale, q_block):
    B, L, D = x.shape
    Hq, Hkv = d["Hq"], d["Hkv"]
    Dh, g = D // Hq, Hq // Hkv
    q = es("bld,de->ble", x, lp["wq"]).reshape(B, L, Hq, Dh)
    k = es("bld,de->ble", x, lp["wk"]).reshape(B, L, Hkv, Dh)
    v = es("bld,de->ble", x, lp["wv"]).reshape(B, L, Hkv, Dh)
    k = jnp.repeat(k, g, axis=2)           # kv head j serves q heads j*g..
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for q0 in range(0, L, q_block):
        qb = q[:, q0:q0 + q_block]
        s = es("bqhd,bkhd->bhqk", qb, k) * scale
        qpos = q0 + jnp.arange(qb.shape[1])[:, None]
        s = jnp.where(jnp.arange(L)[None, :] <= qpos, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(es("bhqk,bkhd->bqhd", p, v))
    o = jnp.concatenate(outs, axis=1).reshape(B, L, D)
    return es("ble,ed->bld", o, lp["wo"])


def last_logits(params, cfg: dict, tokens, last, precision: str = "highest",
                q_block: int = 256):
    """Logits ``[B, V]`` at position ``last[b]`` of each row of
    ``tokens`` ``[B, L]`` (positions past it may hold anything: nothing
    before them reads them)."""
    d = _dims(cfg)
    es = make_einsum(precision)
    r = cfg["residual_multiplier"]
    h = params["embed"][tokens] * cfg["embedding_multiplier"]
    for kind, lp in zip(d["types"], params["layers"]):
        n = _rms(h, lp["norm1"], d["eps"])
        if kind == "mamba":
            out = _mamba(lp, n, d, es)
        else:
            out = _attention(lp, n, d, es, cfg["attention_multiplier"],
                             q_block)
        h = h + r * out
        n = _rms(h, lp["norm2"], d["eps"])
        gu = es("bld,df->blf", n, lp["w_mlp_in"])
        F = d["F"]
        h = h + r * es("blf,fd->bld", jax.nn.silu(gu[..., :F]) * gu[..., F:],
                       lp["w_mlp_out"])
    hl = h[jnp.arange(h.shape[0]), last]
    hl = _rms(hl, params["final_norm"], d["eps"])
    return es("bd,vd->bv", hl, params["embed"]) / cfg["logits_scaling"]
