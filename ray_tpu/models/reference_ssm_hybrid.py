"""The plain reference of the decoder with selective state-space layers
(Mamba-2) beside softmax GQA layers without positions, a dense SwiGLU in every
layer, four scalar multipliers and a tied head, that models/transformer.py
serves (a ``layer_pattern`` with a ``mixer="ssd"`` kind, ``rope_share=0``,
``embed_multiplier``, ``residual_multiplier``, ``attention_multiplier``,
``logits_divisor``, ``tie_embeddings``): the published layer of
granite-4.0-h-micro (``granitemoehybrid`` with no experts), written down once
in float32 ``jax.numpy`` with no kernel, chunk, cache or batching: a Python
loop over layers, the whole [S, S] score matrix masked, the recurrence a
``lax.scan`` over positions. It imports nothing of transformer.py nor of ops/
and reads that module's parameter tree because the weights under test are the
only ones there are: ``kind_layers`` {"attention": the softmax layers,
"mamba": the state-space layers, each stacked in order}, ``embed`` (the head
too) and ``final_norm``. tests/test_ssd.py and tests/test_granite_serving.py
hold the program to it.

    x = embedding_multiplier E[tokens]
    layer l (N an RMSNorm with a learned weight; no bias anywhere but the
    convolution's): x = x + residual_multiplier Mixer(N(x)), then
      x = x + residual_multiplier (silu(h Wg) * (h Wu)) Wd, h = N'(x)
    attention mixer: q = h Wq [H, d]; k = h Wk, v = h Wv [KV, d]; no rope and
      no other position signal; a_h = softmax(attention_multiplier q_h k_g^T
      + causal mask) v_g, g = h // (H / KV); out a Wo
    mamba mixer (H heads of width P, state size N, G groups, T taps):
      [z | u | dt] = h W_in (H P | H P + 2 G N | H); u = silu(conv(u) + b),
      causal and depthwise over time (y_t = sum_j c_j u_(t-T+1+j), zeros
      before position 0); [x | B | C] = u, x [H, P], B and C [G, N];
      dt = softplus(dt + dt_bias), a = exp(-exp(A_log) dt) a head;
      S_t = a_t S_(t-1) + dt_t x_t B_t^T (S [P, N] a head, from 0),
      y_t = S_t C_t + D x_t; y = N_g(y * silu(z)), an RMS norm over all H P
      columns (the gate first); out y W_out
    logits = N_f(x) E^T / logits_scaling

`model` holds the published keys that are numbers (`rms_norm_eps`,
`layer_types`, the four scalars, `mamba_*`). Assumed, and listed in the
benchmark's configuration file: the state and the decays in float32, no
`time_step_limit`, the gate before the norm over one group of all columns.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ATTENTION, MAMBA = "attention", "mamba"


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def attention(h, lp, allowed, scale):
    """h [B, S, D] -> the mixer's output [B, S, D]; allowed [B, S, S]."""
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, lp[name].astype(F32)) for name in ("wq", "wk", "wv"))
    B, S, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, d)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) * scale
    p = jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(B, S, H, d)
    return jnp.einsum("bshk,hkd->bsd", a, lp["wo"].astype(F32))


def mamba_inputs(h, lp, model):
    """-> (z [B,S,H P], x [B,S,H,P], B and C [B,S,G,N], dt and the decay a [B,S,H])."""
    H, P, G, N = model["mamba_n_heads"], model["mamba_d_head"], model["mamba_n_groups"], model["mamba_d_state"]
    I, S = H * P, h.shape[1]
    zxd = h @ lp["w_in"].astype(F32).T  # stored [outputs, D]
    z, u, dt = zxd[..., :I], zxd[..., I:-H], zxd[..., -H:]
    taps = lp["conv"].astype(F32)  # [T, channels], the oldest input's first
    T = taps.shape[0]
    padded = jnp.pad(u, ((0, 0), (T - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[:, j:j + S] * taps[j] for j in range(T)) + lp["conv_bias"].astype(F32))
    x = u[..., :I].reshape(*u.shape[:2], H, P)
    Bm = u[..., I:I + G * N].reshape(*u.shape[:2], G, N)
    Cm = u[..., I + G * N:].reshape(*u.shape[:2], G, N)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(F32))
    return z, x, Bm, Cm, dt, jnp.exp(-jnp.exp(lp["a_log"].astype(F32)) * dt)


def selective_scan(x, Bm, Cm, dt, a):
    """One position a turn from S = 0 -> (y [B,S,H,P], the last state [B,H,P,N])."""
    B, _, H, P = x.shape
    per_head = lambda m: jnp.repeat(m, H // m.shape[2], axis=2)  # a head reads its group's B and C

    def position(s, at):
        x_t, b_t, c_t, dt_t, a_t = at
        s = a_t[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    over_time = tuple(jnp.moveaxis(m, 1, 0) for m in (x, per_head(Bm), per_head(Cm), dt, a))
    s, y = jax.lax.scan(position, jnp.zeros((B, H, P, Bm.shape[-1]), F32), over_time)
    return jnp.moveaxis(y, 0, 1), s


def mamba(h, lp, model, eps):
    z, x, Bm, Cm, dt, a = mamba_inputs(h, lp, model)
    y = selective_scan(x, Bm, Cm, dt, a)[0] + lp["d_skip"].astype(F32)[:, None] * x
    y = _norm(y.reshape(z.shape) * jax.nn.silu(z), lp["o_norm"], eps)
    return jnp.einsum("bshk,hkd->bsd", y.reshape(x.shape), lp["wo"].astype(F32))


def ffn(x, lp):
    return (jax.nn.silu(x @ lp["w_gate"].astype(F32)) * (x @ lp["w_up"].astype(F32))) @ lp["w_down"].astype(F32)


def logits(params, tokens, model: dict):
    """tokens [B, S] -> logits [B, S, V], float32: every layer in order."""
    eps, r = float(model["rms_norm_eps"]), float(model["residual_multiplier"])
    with jax.default_matmul_precision("highest"):
        B, S = tokens.shape
        allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
        embed = params["embed"].astype(F32)
        x = float(model["embedding_multiplier"]) * embed[tokens]
        seen = {ATTENTION: 0, MAMBA: 0}
        for kind in model["layer_types"]:
            lp = {k: v[seen[kind]] for k, v in params["kind_layers"][kind].items()}
            seen[kind] += 1
            h = _norm(x, lp["attn_norm"], eps)
            mixed = (attention(h, lp, allowed, float(model["attention_multiplier"])) if kind == ATTENTION
                     else mamba(h, lp, model, eps))
            x = x + r * mixed
            x = x + r * ffn(_norm(x, lp["ffn_norm"], eps), lp)
        return _norm(x, params["final_norm"], eps) @ embed.T / float(model["logits_scaling"])
