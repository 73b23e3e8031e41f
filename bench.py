"""Headline benchmark: transformer LM training throughput on real TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric = model-FLOPs utilization (MFU) of the flagship decoder-only LM train
step on the attached chip(s). The reference publishes no TPU numbers
(BASELINE.md); the north-star target there is >=40% MFU for Train — so
vs_baseline is MFU / 0.40.
"""
from __future__ import annotations

import json
import time


def _peak_flops_per_chip() -> float:
    """bf16 peak FLOP/s for the attached TPU generation. Hard-fails on an
    unrecognized chip: an MFU against a guessed peak is worse than no number
    (a v6e misread as v5e would inflate MFU ~4.7x)."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    # Public peak bf16 numbers (per chip).
    table = {
        "v6e": 918e12,
        "v6": 918e12,
        "v5e": 197e12,
        "v5 lite": 197e12,
        "v5litepod": 197e12,
        "v5p": 459e12,
        "v4": 275e12,
        "v3": 123e12,
        "v2": 45e12,
    }
    for name, peak in table.items():
        if name in kind:
            return peak
    raise RuntimeError(
        f"unrecognized TPU device_kind {kind!r}: add its bf16 peak to the "
        "table in bench.py — refusing to guess (MFU would be wrong)"
    )


def main():
    import jax
    import jax.numpy as jnp

    from ray_tpu.accel.device import enable_compile_cache
    from ray_tpu.models import TransformerConfig, make_train_step
    from ray_tpu.parallel import MeshSpec, ShardingStrategy, logical_sharding, shard_pytree
    from ray_tpu.parallel.sharding import use_strategy

    enable_compile_cache()
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures the chip; this process runs on "
            f"{jax.default_backend()!r} and there is no CPU variant"
        )
    n_dev = len(jax.devices())

    # ~250M-param Llama-style GQA model sized for one v5e chip (16 GB HBM).
    # n_kv_heads=4: the flash kernel reads grouped K/V natively (no repeat),
    # measured +8% tokens/sec over full-head KV on v5e.
    cfg = TransformerConfig(
        vocab_size=32_000,
        d_model=1024,
        n_layers=12,
        n_heads=16,
        n_kv_heads=4,
        d_ff=4096,
        max_seq_len=2048,
        remat=True,
        # Round-4 tuning (PROFILES.md): 1024x1024 flash tiles (the profiler
        # trace showed the 512x512 kernels at ~30% efficiency eating 18% of
        # the step) + dots-saveable remat policy. 0.45 -> 0.52 MFU on v5e.
        remat_policy="dots",
        attention_impl="auto",
        attention_block_q=1024,
        attention_block_k=1024,
    )
    batch, seq = 16, 2048

    mesh = MeshSpec(data=-1).build()
    strategy = ShardingStrategy.dp() if n_dev > 1 else ShardingStrategy.none()

    init_state, train_step, state_axes = make_train_step(cfg)
    with use_strategy(strategy), mesh:
        state = init_state(jax.random.PRNGKey(0))
        axes = state_axes(state)
        state = shard_pytree(state, axes, mesh, strategy)
        state_sh = logical_sharding(mesh, strategy, axes)
        batch_sh = strategy.sharding(mesh, ("batch", "seq"))
        tokens = jax.device_put(
            jax.random.randint(jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size),
            batch_sh,
        )
        data = {"tokens": tokens}
        step = jax.jit(
            train_step,
            in_shardings=(state_sh, {"tokens": batch_sh}),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        # warmup / compile. Sync via host transfer of the loss: chosen when
        # block_until_ready was not a reliable fence on an earlier stack;
        # PERF.md "Bring-up" has what the current one measures.
        state, m = step(state, data)
        _ = float(m["loss"])
        iters = 20
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m = step(state, data)
        loss_val = float(m["loss"])
        dt = (time.perf_counter() - t0) / iters

    # Model FLOPs: 6 * params * tokens (fwd+bwd) + attention term
    # 12 * L * d * S^2 * B ... use standard 6ND + 12*L*H*hd*S^2.
    n_params = sum(x.size for x in jax.tree.leaves(state["params"]))
    tokens_per_step = batch * seq
    flops = 6.0 * n_params * tokens_per_step + 12.0 * cfg.n_layers * cfg.d_model * seq * tokens_per_step
    mfu = flops / dt / (_peak_flops_per_chip() * n_dev)
    tokens_per_sec = tokens_per_step / dt

    print(json.dumps({
        "metric": "train_mfu_flagship_lm",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak_bf16",
        "vs_baseline": round(mfu / 0.40, 4),
        "detail": {
            "tokens_per_sec_per_chip": round(tokens_per_sec / n_dev, 1),
            "step_time_s": round(dt, 4),
            "params": n_params,
            "batch": batch,
            "seq": seq,
            "n_devices": n_dev,
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "peak_flops_per_chip": _peak_flops_per_chip(),
            "final_loss": round(loss_val, 4),
        },
    }))


if __name__ == "__main__":
    main()
