"""The serve cells' comparison with the plain reference, as arithmetic on
arrays: the replica computes the sets of logits (harness/replica.py), the
tests and the control put other forwards in the program's place
(benchmarks/tests/)."""
from __future__ import annotations

import numpy as np

COARSE_MANTISSA_BITS = 4
# The most the forward under test may err, as a share of what the reference
# itself errs when its weights keep COARSE_MANTISSA_BITS mantissa bits.
NOISE_LIMIT = 0.4


def coarse_weights(params, mantissa_bits: int = COARSE_MANTISSA_BITS):
    """The parameter tree with every weight kept in 4 mantissa bits
    (bfloat16 has 7, fp8 e4m3 has 3): the yardstick of `judge`. The control
    (benchmarks/tests/control.py) keeps fewer."""
    import jax

    return jax.tree.map(lambda a: jax.lax.reduce_precision(a, 8, mantissa_bits), params)


def judge(ref, own, coarse, served) -> dict:
    """ref: [n, V] logits of the plain float32 reference at the n generated
    positions; own: the forward under test there (the program's, in the
    precision the configuration states: bf16 activations); coarse: the
    reference again, from coarse_weights; served: the n tokens the served
    path chose (greedy).

    Three tests. The reference's logits are finite. A served token may trail
    the reference's best logit by twice the forward's own error
    `noise` = max |own - ref| (bf16's rounding flips near-ties). And `noise`
    may be at most NOISE_LIMIT of max |coarse - ref|: a forward in a lower
    precision than the configuration states widens the second test with its
    own error, and the third is what catches it.

    Why a share of the coarse reference's error and not of the logits' scale
    (the bound was `noise <= 0.05 * scale` until PR 26): how far rounding
    moves the logits depends on the weights more than on anything the
    configuration states. As a share of the scale the bf16 forward read
    0.0098-0.0127 at 2 layers, 0.0125-0.0156 at 8 and 0.0138-0.0190 at 24
    (internlm2-1.8b cut to depth, one chip, 12 seeds each), 0.0085-0.0111 /
    0.0109-0.0140 at 2 / 8 layers of mistral-7b-v0.3 on one chip,
    0.0117-0.0152 at 8 and 0.0075-0.0101 at 32 over four chips: neither depth
    nor sharding moves it much. But one seed's weights (3000000019, the 32
    layers over four chips) read 0.0593, six times their neighbours, at every
    position, twice over (PR 24's run and PR 26's), and 0.0003 with float32
    activations: rounding, amplified by that draw of weights. The coarse
    reference is amplified alike (0.369 of the scale there, 0.031-0.061 for
    the other seeds), so the share is steady where the level is not: over 97
    draws of those seven shapes (the cells' own runs among them) the bf16
    forward read 0.035-0.274 of the coarse error (0.161 for seed
    3000000019). The control, the reference from weights in 3 mantissa bits
    (fp8 e4m3's, the step below bfloat16), read 0.540-1.601 at 24 layers of
    internlm2-1.8b (12 seeds); the coarse reference itself, put in the
    program's place, reads 1. NOISE_LIMIT lies between: 1.46 x the largest
    sound reading, 0.74 of the smallest control (TPU v5 lite, my chip runs,
    PR 26; PERF.md section 2 has the table)."""
    ref, own, coarse = (np.asarray(a, np.float32) for a in (ref, own, coarse))
    n = len(served)
    err = np.abs(own - ref)
    noise, yard = float(err.max()), float(np.abs(coarse - ref).max())
    chosen = ref[np.arange(n), np.asarray(served)]
    trail = float((ref.max(-1) - chosen).max())
    return {"bf16_logit_error": noise, "worst_trail": trail, "logit_scale": float(np.abs(ref).max()),
            "coarse_logit_error": yard, "noise_share_of_coarse": noise / yard, "noise_limit": NOISE_LIMIT,
            "position_noise": [round(float(x), 4) for x in err.max(-1)], "tokens": n,
            "ok": bool(np.isfinite(ref).all() and trail <= 2 * noise and noise <= NOISE_LIMIT * yard)}
