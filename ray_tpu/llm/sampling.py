"""Per-request sampling parameters + the batched device sampler.

Reference: vLLM-style per-request SamplingParams carried through the engine
(the reference's llm serving passes them per request to vLLM,
llm/_internal/serve/core/server/llm_server.py); here every decode step
samples ALL slots in one program, so the parameters ride as [B] device
arrays and the sampler is vectorized per row — one mixed batch can hold
greedy, temperature, top-k, and nucleus rows simultaneously with no
recompilation (array contents, not static jit args).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# Static candidate cap for truncated (top-k / top-p) rows: XLA needs a fixed
# shape, and a 128-candidate top_k covers every practical top_k and the
# nucleus mass of peaked LM distributions. Rows with top_p>=1 & top_k off
# bypass it and sample the full distribution exactly. CAVEAT: a high-entropy
# distribution with top_p just below 1 has a nucleus wider than the cap; the
# sampled distribution is then the renormalized top-`cap`, not the true
# nucleus — raise EngineConfig.sample_topk_cap when that matters.
TOPK_CAP = 128


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode controls (every field optional).

    temperature: 0 => greedy. top_k: 0 => disabled. top_p: 1.0 => disabled.
    stop_token_ids: extra per-request stop tokens (checked host-side at
    absorb time, like the engine-global eos). stop: stop STRINGS — applied
    by the text layer (deployment/ingress) after detokenization, since the
    engine speaks tokens. max_tokens: generation budget.
    """

    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    max_tokens: int = 64
    stop_token_ids: tuple = ()
    stop: tuple = ()
    # Engine-global eos still applies; set ignore_eos for benchmarks that
    # must generate exactly max_tokens (reference: vLLM ignore_eos).
    ignore_eos: bool = False

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if not 0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if self.max_tokens <= 0:
            raise ValueError(f"max_tokens must be > 0, got {self.max_tokens}")


def sample_batch(logits, temps, top_ps, top_ks, key, cap: int | None = None):
    """Sample one token per row of logits [B, V] under per-row params.

    Rows with temps<=0 take argmax. Truncated rows (top_k>0 or top_p<1)
    sample among the top-`cap` candidates (default TOPK_CAP=128; see its
    caveat) after top-k and nucleus masking; plain-temperature rows sample
    the full distribution.

    What is computed when: the argmax always; the truncated candidate (the
    top-`cap` of [B, V], its softmax and draw) only if some row of the batch
    is a truncated row, and the full one (Gumbel noise over [B, V]) only if
    some row is a plain-temperature row. Each sits under a `lax.cond` on the
    params the program already holds, so an all-greedy batch pays for the
    argmax alone, and a row's token is the same expression of the same key
    whatever the other rows ask for.

    Must not be called under `vmap`: there a `cond` becomes a `select` that
    computes both sides, for every row of every step. The engine reaches it
    only under `lax.scan` (tests/test_llm_sampling.py holds that).
    """
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    cap = min(TOPK_CAP if cap is None else cap, V)
    k1, k2 = jax.random.split(key)
    sampled = temps > 0.0
    plain = (top_ps >= 1.0) & (top_ks <= 0)

    def scaled():  # inside the branches: an operand of a `cond` is computed before it
        return logits / jnp.maximum(temps, 1e-6)[:, None]

    def draw_truncated():
        top_vals, top_idx = jax.lax.top_k(scaled(), cap)  # [B, cap], descending
        ks = jnp.where(top_ks <= 0, cap, jnp.minimum(top_ks, cap))
        pos = jnp.arange(cap)[None, :]
        masked = jnp.where(pos < ks[:, None], top_vals, -jnp.inf)
        probs = jax.nn.softmax(masked, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_ps[:, None]  # prefix mass before the token
        masked = jnp.where(keep, masked, -jnp.inf)  # first candidate always kept
        choice = jax.random.categorical(k1, masked, axis=-1)
        return jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)

    def draw_full():
        return jax.random.categorical(k2, scaled(), axis=-1).astype(jnp.int32)

    def nobody():
        return jnp.zeros(B, jnp.int32)

    truncated = jax.lax.cond(jnp.any(sampled & ~plain), draw_truncated, nobody)
    full = jax.lax.cond(jnp.any(sampled & plain), draw_full, nobody)
    out = jnp.where(plain, full, truncated)
    return jnp.where(temps <= 0.0, greedy, out).astype(jnp.int32)
