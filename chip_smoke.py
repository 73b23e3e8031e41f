"""Proof that the system starts on the chip: kernels, a trainer, a server.

    python chip_smoke.py                the chip run; fails without a TPU
    python chip_smoke.py --rehearsal    same control flow, toy sizes, CPU,
                                        interpreted kernels; proves nothing
                                        about the chip and says so

Four phases, each in its own process, one after the other, because a chip
belongs to one process at a time. This parent never touches JAX:

- kernels: the Pallas kernels, Mosaic-compiled, against the jnp references.
- train:   ray_tpu.train.JaxTrainer(...).fit(); the one train worker holds
           every local chip and takes a compile step plus five steps.
- repeat:  the exact prefix-cache hit on the engine itself: what must a
           repeated prompt return, when in bf16 it need not be the cold
           send's tokens again? The serve phase is held to the answer.
- serve:   rt.init / serve.start / serve.run(build_llm_app(...)), streaming
           HTTP requests through the proxy; the replica holds the chip.

The model is the flagship preset (MODEL below) at full width and depth, weights
random from a seed. Step times printed here are information, not results.
The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150  # the whole run, compilation included
RESULT_TAG = "PHASE_RESULT "

MODEL = dict(vocab_size=32_000, d_model=1024, n_layers=12, n_heads=16,
             n_kv_heads=4, d_ff=4096, max_seq_len=2048, attention_impl="auto")
CHIP = dict(
    model=MODEL,
    flash=dict(B=2, S=2048, H=16, KV=4, D=64, tile=1024),
    paged=dict(B=32, H=16, KV=4, D=64, page=128, pages_per_seq=16),
    # a chip's share of the four-chip serve cell: 2 KV heads, 32 pages a slot
    paged_sparse=dict(B=32, H=8, KV=2, D=128, page=128, pages_per_seq=32),
    train=dict(remat=True, remat_policy="dots", attention_block_q=1024,
               attention_block_k=1024),
    batch_per_chip=16, seq=2048, steps=5,
    engine=dict(max_slots=32, max_seq=2048, page_size=128,
                prefix_cache=True, prefill_buckets=(128, 256, 512, 1024)),
    warmup_buckets=(512,), prompt_len=512, shared_prefix=384, new_tokens=64,
)
TOY_MODEL = dict(vocab_size=512, d_model=256, n_layers=2, n_heads=4,
                 n_kv_heads=2, d_ff=256, max_seq_len=256, attention_impl="auto")
REHEARSAL = dict(
    model=TOY_MODEL,
    flash=dict(B=1, S=256, H=4, KV=2, D=64, tile=128),
    paged=dict(B=4, H=4, KV=2, D=64, page=32, pages_per_seq=4),
    paged_sparse=dict(B=6, H=4, KV=2, D=64, page=16, pages_per_seq=8),
    train=dict(remat=True, remat_policy="dots", attention_block_q=128,
               attention_block_k=128),
    batch_per_chip=2, seq=256, steps=5,
    engine=dict(max_slots=4, max_seq=256, page_size=32,
                prefix_cache=True, prefill_buckets=(32, 64, 128)),
    warmup_buckets=(128,), prompt_len=96, shared_prefix=64, new_tokens=8,
)
N_REQUESTS = 8
# Kernel output vs an f32 oracle on the same bf16 inputs: two bf16 ulps at
# the oracle's largest magnitude (2^-6 relative to max|ref|).
KERNEL_TOL = 2.0 ** -6
# The same K/V vector from two bf16 programs, after up to a model's depth of
# layers: each carries the residual stream's accumulated rounding (at full
# width layer 0 agrees bit for bit and layer 11 to 0.018 of the magnitude on
# the chip, 0.022 on CPU with the jnp attention; PR 21). Derived from
# another token or position, the vector is off by its own magnitude, ~1.
REDERIVED_TOL = 2.0 ** -4
MOSAIC_CALL = "tpu_custom_call"


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Who holds the chip: read from /proc, so the program cannot talk its way out
# ---------------------------------------------------------------------------

def _session_pids(sid: int) -> list[int]:
    """Every live process of a session (each phase leads its own). Live means
    some thread has yet to stop: a killed process's leader turns zombie
    while its other threads are still closing the files they share, the
    chip's among them, so the leader's state alone says nothing."""
    out = []
    for name in os.listdir("/proc"):
        if name.isdigit():
            try:
                if os.getsid(int(name)) != sid:
                    continue
                for tid in os.listdir(f"/proc/{name}/task"):
                    with open(f"/proc/{name}/task/{tid}/stat") as f:
                        if f.read().rsplit(")", 1)[1].split()[0] not in "ZX":
                            out.append(int(name))
                            break
            except (OSError, IndexError):
                pass
    return sorted(out)


def _chip_files_held(pid: int) -> list[str]:
    """TPU device files this process holds open (accel/tpu.chip_device_files:
    the backend opens them when it initialises, and not before)."""
    held = set()
    try:
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if re.match(r"/dev/(accel|vfio/)\d+$", target):
                held.add(target)
    except OSError:
        pass
    return sorted(held)


def _chips_free() -> bool:
    """Whether every chip of this host can be opened. A vfio group opens for
    one holder at a time, so EBUSY says that some process, of whatever
    session, has yet to let go (a phase's cluster starts its workers in
    sessions of their own, which _session_pids(phase) does not list)."""
    import errno

    from ray_tpu.accel.tpu import chip_device_files

    for path in chip_device_files():
        try:
            os.close(os.open(path, os.O_RDWR))
        except OSError as e:
            if e.errno == errno.EBUSY:
                return False
    return True


def audit_processes(roles: dict[int, str]) -> dict[int, list[str]]:
    """Print one line per process of this phase: its role and the chip
    device files it holds. Returns {pid: files} for the holders."""
    import ray_tpu.state as state

    by_worker = {a["worker_id"]: a["name"] or a["class"].rsplit(".", 1)[-1]
                 for a in state.list_actors(limit=1000)["actors"] if a["state"] == "ALIVE"}
    holders = {}
    for pid in _session_pids(os.getsid(0)):
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = dict(kv.split(b"=", 1) for kv in f.read().split(b"\0") if b"=" in kv)
        except OSError:
            continue
        wid = env.get(b"RAYTPU_WORKER_ID", b"").decode()
        role = roles.get(pid) or (by_worker.get(wid, "idle worker") if wid else "other")
        files = _chip_files_held(pid)
        if files:
            holders[pid] = files
        say(f"  pid {pid:>7}  {role:<40} chip files held: {files or 'none'}")
    return holders


# ---------------------------------------------------------------------------
# Phase: kernels
# ---------------------------------------------------------------------------

def phase_kernels(sz: dict, rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.accel.device import device_report, enable_compile_cache
    from ray_tpu.ops.attention import flash_attention, mha_reference
    from ray_tpu.ops.paged_attention import kv_row_width, paged_attention, paged_attention_reference

    cache_dir = enable_compile_cache()
    out = {**device_report(), "compile_cache_dir": cache_dir}
    _require_platform(out, rehearsal)
    interpret = rehearsal
    failures, checks, compile_s = [], {}, 0.0

    def check(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err, scale = float(np.abs(got - want).max()), max(1.0, float(np.abs(want).max()))
        ok = bool(np.isfinite(got).all()) and err <= KERNEL_TOL * scale
        checks[name] = {"max_err": err, "bound": KERNEL_TOL * scale, "ok": ok}
        say(f"  {'ok  ' if ok else 'FAIL'} {name}: max|err| {err:.4g} <= {KERNEL_TOL * scale:.4g}")
        if not ok:
            failures.append(name)

    def timed_compile(fn, *args):
        nonlocal compile_s
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s += time.perf_counter() - t0
        if not interpret and MOSAIC_CALL not in compiled.as_text():
            failures.append(f"{fn.__name__}: no Mosaic custom call in the compiled program")
        return compiled

    f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731

    # flash attention, forward and grad, with and without segment ids
    fl = sz["flash"]
    B, S, H, KV, D, tile = (fl[k] for k in ("B", "S", "H", "KV", "D", "tile"))
    kq, kk, kv_, kd = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.bfloat16)
    v = jax.random.normal(kv_, (B, S, KV, D), jnp.bfloat16)
    do = jax.random.normal(kd, (B, S, H, D), jnp.float32)
    bounds = jnp.array([S // 3, (2 * S) // 3])[:B]
    segs = (jnp.arange(S)[None, :] >= bounds[:, None]).astype(jnp.int32)
    for label, seg in (("", None), ("+segment_ids", segs)):
        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, segment_ids=seg,
                                   block_q=tile, block_k=tile, interpret=interpret)

        def oracle(q, k, v):
            return mha_reference(q, k, v, causal=True, segment_ids=seg)

        def loss_of(fn):
            return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * do).sum()

        def flash_grad(q, k, v):
            return jax.grad(loss_of(flash), argnums=(0, 1, 2))(q, k, v)

        check(f"flash fwd{label}", timed_compile(flash, q, k, v)(q, k, v),
              jax.jit(oracle)(*f32(q, k, v)))
        got = timed_compile(flash_grad, q, k, v)(q, k, v)
        want = jax.jit(jax.grad(loss_of(oracle), argnums=(0, 1, 2)))(*f32(q, k, v))
        for name, g, w in zip(("dq", "dk", "dv"), got, want):
            check(f"flash {name}{label}", g, w)

    # paged decode attention: the last of three layers' pools, told to the
    # kernel as the engine's layer loop tells it (a traced index), and the
    # token's own K/V written into the pools by the call. Dead table entries,
    # and the whole row of a slot in `empty` (length 0: no sequence, nothing
    # written, zeros returned), name page 0.
    def paged_check(label, pg, lens, empty=()):
        B, H, KV, D, ps, ppseq = (pg[k] for k in ("B", "H", "KV", "D", "page", "pages_per_seq"))
        used = [0 if b in empty else -(-int(lens[b]) // ps) for b in range(B)]
        n_pages, n_layers = sum(used) + 1, 3
        kq, kk, kv_, kn, vn = jax.random.split(jax.random.PRNGKey(1), 5)
        q = jax.random.normal(kq, (B, H, D), jnp.bfloat16)
        # a head under a lane tile lies in rows of a whole one, zeros behind it, as the engine's pools do
        pad = ((0, 0),) * 4 + ((0, kv_row_width(D) - D),)
        kp = jnp.pad(jax.random.normal(kk, (n_layers, KV, n_pages, ps, D), jnp.bfloat16), pad)
        vp = jnp.pad(jax.random.normal(kv_, (n_layers, KV, n_pages, ps, D), jnp.bfloat16), pad)
        k_new = jax.random.normal(kn, (B, KV, D), jnp.bfloat16)
        v_new = jax.random.normal(vn, (B, KV, D), jnp.bfloat16)
        table = np.zeros((B, ppseq), np.int32)
        free = iter(np.random.default_rng(0).permutation(np.arange(1, n_pages)))
        for b in range(B):
            table[b, :used[b]] = [next(free) for _ in range(used[b])]
        lens, table, layer = jnp.asarray(lens, jnp.int32), jnp.asarray(table), jnp.int32(n_layers - 1)

        def paged(q, k_new, v_new, kp, vp, lens, table, layer):
            return paged_attention(q, k_new, v_new, kp, vp, lens, table, layer, interpret=interpret)

        args = (q, k_new, v_new, kp, vp, lens, table, layer)
        got = timed_compile(paged, *args)(*args)
        want = jax.jit(paged_attention_reference)(*f32(q, k_new, v_new, kp, vp), lens, table, layer)
        check(f"paged decode{label}", got[0], want[0])
        if empty and np.asarray(got[0], np.float32)[sorted(empty)].any():
            failures.append(f"paged decode{label}: a row without a sequence is not zeros")
        for name, g, w in zip(("K", "V"), got[1:], want[1:]):
            check(f"paged decode{label}: {name} pool", g, w)

    # ragged lengths, a one-token and a full sequence, and a partial last page
    pg = sz["paged"]
    full = pg["pages_per_seq"] * pg["page"]
    lens = np.random.default_rng(0).integers(1, full + 1, pg["B"])
    lens[:3] = (1, full, full // 2 + pg["page"] // 3)
    paged_check("", pg, lens)
    # the walk's edge: most slots empty (length 0: no grid step), beside
    # a full table, exactly one page, and a page's first and last row
    pg = sz["paged_sparse"]
    ps, lens = pg["page"], np.zeros(pg["B"], np.int64)
    live = {1: pg["pages_per_seq"] * ps, 2: ps, 4: ps + 1, pg["B"] - 1: 3 * ps}
    for b, n in live.items():
        lens[b] = n
    paged_check(", sparse", pg, lens, empty=set(range(pg["B"])) - set(live))

    # One dispatch's round trip: a trivial program, dispatched and awaited.
    bump = jax.jit(lambda x: x + 1)
    x = jax.block_until_ready(bump(jnp.zeros((8, 128), jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(200):
        x = jax.block_until_ready(bump(x))
    out["dispatch_roundtrip_us"] = round((time.perf_counter() - t0) / 200 * 1e6, 1)
    say(f"  one dispatch, awaited: {out['dispatch_roundtrip_us']} us (trivial program, mean of 200)")

    out.update(compile_s=round(compile_s, 2), checks=checks, failures=failures,
               kernels="interpreted (rehearsal)" if interpret else "Mosaic-compiled")
    return out


# ---------------------------------------------------------------------------
# Phase: train
# ---------------------------------------------------------------------------

def _train_fn(config: dict) -> None:
    """Runs in the train worker: make_train_step's step, a few steps."""
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.accel.device import device_report, enable_compile_cache
    from ray_tpu.models import TransformerConfig, make_train_step
    from ray_tpu.parallel import MeshSpec, ShardingStrategy, logical_sharding, shard_pytree
    from ray_tpu.parallel.sharding import use_strategy

    cache_dir = enable_compile_cache()
    report = device_report()
    n_dev = report["device_count"]
    cfg = TransformerConfig(**config["model"], **config["train"])
    batch, seq = config["batch_per_chip"] * n_dev, config["seq"]

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
        t0 = time.perf_counter()
        compiled = step.lower(state, data).compile()
        compile_s = time.perf_counter() - t0
        hlo = compiled.as_text()

        def shard_shape(x):
            return list(x.addressable_shards[0].data.shape)

        layout = {
            name: {"global": list(x.shape), "per_device": shard_shape(x),
                   "devices": len(x.sharding.device_set)}
            for name, x in (("wq", state["params"]["layers"]["wq"]),
                            ("adam_mu_wq", state["opt"][0].mu["layers"]["wq"]),
                            ("tokens", tokens))
        }
        # Result shapes of each Mosaic call in the per-device program: the
        # flash kernels' outputs lead with batch_on_this_chip * heads.
        mosaic_results = sorted({
            re.sub(r"\{[^}]*\}", "", m.group(1))
            for line in hlo.splitlines() if MOSAIC_CALL in line
            for m in [re.search(r"= (.*?) custom-call\(", line)] if m
        })
        train.report({
            **report, "compile_cache_dir": cache_dir, "compile_s": round(compile_s, 2),
            "mosaic_in_step": MOSAIC_CALL in hlo, "mosaic_results": mosaic_results,
            "layout": layout, "batch": batch, "seq": seq,
        })
        for i in range(1 + config["steps"]):
            t0 = time.perf_counter()
            state, m = compiled(state, data)
            jax.block_until_ready(m["loss"])
            t_fence = time.perf_counter() - t0
            loss = float(np.asarray(m["loss"]))  # host copy: the older fence
            t_copy = time.perf_counter() - t0 - t_fence
            train.report({"step": i, "loss": loss, "step_s": round(t_fence, 4),
                          "host_copy_after_fence_s": round(t_copy, 6)})


def phase_train(sz: dict, rehearsal: bool, chips: int) -> dict:
    import math
    import tempfile

    import ray_tpu as rt
    from ray_tpu import train
    from ray_tpu.accel.device import backend_initialized

    rt.init(num_cpus=4, resources={"TPU": chips} if chips else None)
    try:
        trainer = train.JaxTrainer(
            _train_fn,
            train_loop_config={k: sz[k] for k in ("model", "train", "batch_per_chip", "seq", "steps")},
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=not rehearsal,
                resources_per_worker={"TPU": chips} if chips else {"CPU": 1},
            ),
            run_config=train.RunConfig(name="chip_smoke", storage_path=tempfile.mkdtemp()),
        )
        result = trainer.fit()
        if result.error:
            raise RuntimeError(f"train worker failed:\n{result.error}")
        head, steps = result.metrics_history[0], result.metrics_history[1:]
    finally:
        rt.shutdown()
    out = dict(head)
    _require_platform(out, rehearsal)
    losses = [s["loss"] for s in steps]
    failures = []
    if len(steps) != 1 + sz["steps"]:
        failures.append(f"expected {1 + sz['steps']} steps, saw {len(steps)}")
    if not all(math.isfinite(x) for x in losses):
        failures.append(f"loss not finite: {losses}")
    elif not losses[-1] < losses[0]:
        failures.append(f"loss did not fall: {losses[0]} -> {losses[-1]}")
    if not rehearsal and not head["mosaic_in_step"]:
        failures.append("the compiled train step holds no Mosaic custom call")
    if backend_initialized():
        failures.append("the train driver initialised a JAX backend")
    per_dev = head["layout"]["tokens"]["per_device"][0]
    if per_dev != sz["batch_per_chip"]:
        failures.append(f"batch rows per device {per_dev} != {sz['batch_per_chip']}")
    say(f"  losses: {[round(x, 4) for x in losses]}")
    say(f"  step seconds (information, not a result): {[s['step_s'] for s in steps[1:]]}")
    say(f"  host copy of the loss after block_until_ready: "
        f"{max(s['host_copy_after_fence_s'] for s in steps[1:]) * 1e6:.0f} us at most")
    say(f"  layout: {json.dumps(head['layout'])}")
    say(f"  Mosaic calls in the per-device step return: {head['mosaic_results']}")
    rows = sz["batch_per_chip"] * sz["model"]["n_heads"]
    if not rehearsal and not any(f"[{rows},{sz['seq']}," in r for r in head["mosaic_results"]):
        failures.append(f"no flash call over this chip's {rows} (batch x head) rows: "
                        f"{head['mosaic_results']}")
    out.update(losses=losses, step_s=[s["step_s"] for s in steps], failures=failures,
               driver_backend_initialized=backend_initialized())
    return out


# ---------------------------------------------------------------------------
# Phase: serve
# ---------------------------------------------------------------------------

def _prompts(sz: dict) -> tuple[list, list]:
    """The seeded prompts every serving check uses: N_REQUESTS - 2 distinct
    ones, and one that shares the first prompt's leading tokens."""
    import numpy as np

    vocab, P = sz["model"]["vocab_size"], sz["prompt_len"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, P).tolist() for _ in range(N_REQUESTS - 2)]
    shared = prompts[0][: sz["shared_prefix"]] + rng.integers(
        0, vocab, P - sz["shared_prefix"]).tolist()
    return prompts, shared


def _post_stream(port: int, tokens: list, max_tokens: int) -> tuple[int, list]:
    """One streaming POST /llm through the proxy -> (status, new token ids)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", "/llm", json.dumps(
            {"tokens": tokens, "max_tokens": max_tokens, "stream": True}))
        resp = conn.getresponse()
        body = resp.read().decode()
    finally:
        conn.close()
    out = []
    for frame in body.split("\n\n"):
        if frame.startswith("data: ") and frame != "data: [DONE]":
            out += json.loads(frame[6:]).get("new_tokens", [])
    return resp.status, out


def phase_serve(sz: dict, rehearsal: bool, chips: int, expect: dict,
                tensor_parallel: int = 1) -> dict:
    """expect: the repeat phase's findings on the engine — the first
    prompt's last token, and the continuations this phase must now get for
    it through the proxy: "cold" for the first send, "hit" for the repeat."""
    from concurrent.futures import ThreadPoolExecutor

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.accel.device import backend_initialized
    from ray_tpu.llm import build_llm_app

    engine = dict(sz["engine"], tensor_parallel=tensor_parallel)
    vocab = sz["model"]["vocab_size"]
    prompts, shared = _prompts(sz)
    prompts[0][-1] = expect["last_token"]

    rt.init(num_cpus=8, resources={"TPU": chips} if chips else None)
    failures = []
    try:
        serve.start()
        t0 = time.perf_counter()
        serve.run(
            build_llm_app(
                model_config=sz["model"], engine_config=engine,
                warmup_buckets=sz["warmup_buckets"],
                # Zero in a rehearsal: schedulable anywhere, and unchecked.
                ray_actor_options={"resources": {"TPU": chips}},
            ),
            name="smoke", route_prefix="/llm", timeout_s=900,
        )
        ready_s = time.perf_counter() - t0
        replica = serve.get_deployment_handle("llm", "smoke")
        out = replica.device_report.remote().result(timeout=600)
        _require_platform(out, rehearsal)
        port = serve.http_port()
        # The first prompt alone, so that it has retired into the prefix
        # cache before its exact repeat and its 384-token-prefix sibling.
        first = _post_stream(port, prompts[0], sz["new_tokens"])
        with ThreadPoolExecutor(N_REQUESTS) as pool:
            rest = list(pool.map(
                lambda p: _post_stream(port, p, sz["new_tokens"]),
                [prompts[0], shared] + prompts[1:]))
        replies = [first] + rest
        for i, (status, toks) in enumerate(replies):
            if status != 200 or len(toks) != sz["new_tokens"] or not all(
                    isinstance(t, int) and 0 <= t < vocab for t in toks):
                failures.append(f"request {i}: status {status}, {len(toks)} tokens")
        # The exact repeat skips prefill (pages copied from the cache, P-1
        # re-derived by the decode program), so in bf16 it need not equal
        # the cold send token for token. What it must equal the repeat phase
        # established on the engine: the continuation of a request that
        # decoded its way to the same position. Programs are deterministic
        # and rows of a batch independent, so the same tokens must come back
        # through the proxy, whatever else the replica is serving.
        for name, got in (("cold", first[1]), ("hit", rest[0][1])):
            if got != expect[name]:
                at = next((i for i, (a, b) in enumerate(zip(got, expect[name])) if a != b),
                          min(len(got), len(expect[name])))
                failures.append(f"the {name} send parts from the engine's own at token {at}: "
                                f"{got[at:at + 2]} vs {expect[name][at:at + 2]}")
        agreed = next((i for i, (a, b) in enumerate(zip(first[1], rest[0][1])) if a != b),
                      len(first[1]))
        cache = replica.stats.remote().result(timeout=60)["prefix_cache"]
        if not (cache["hits"] >= 1 and cache["partial_hits"] >= 1):
            failures.append(f"prefix cache saw no exact and partial hit: {cache}")
        want = [sz["model"]["n_heads"] // tensor_parallel, sz["model"]["n_kv_heads"] // tensor_parallel]
        got = [out["per_device"]["wq"][2], out["per_device"]["k_pages"][1]]
        if got != want:
            failures.append(f"heads on one device: wq/k_pages {got}, expected {want}")
        if not rehearsal and not (out["mosaic"].get("prefill") and out["mosaic"].get("decode")):
            failures.append(f"a serving program holds no Mosaic custom call: {out['mosaic']}")
        say("  processes while the app is up:")
        holders = audit_processes({os.getpid(): "driver + controller + node daemon",
                                   out["pid"]: "LLM replica"})
        if not rehearsal and set(holders) != {out["pid"]}:
            failures.append(f"chip holders {sorted(holders)} != the replica [{out['pid']}]")
        if backend_initialized():
            failures.append("the serve driver initialised a JAX backend")
    finally:
        serve.shutdown()
        rt.shutdown()
    say(f"  {len(replies)} replies, first tokens of request 0: {first[1][:8]}")
    say(f"  cold send == the engine's cold: {first[1] == expect['cold']}; exact repeat == the "
        f"engine's hit (== its ordinary decode): {rest[0][1] == expect['hit']}")
    say(f"  exact repeat agreed with its first send on {agreed} of {len(first[1])} greedy tokens "
        f"(information: position P-1 comes from another program)")
    say(f"  prefix cache: {cache}")
    say(f"  one device holds: {out['per_device']}")
    out.update(compile_s=out.pop("warmup_s"), ready_s=round(ready_s, 2), failures=failures,
               prefix_cache=cache, holders={str(k): v for k, v in holders.items()},
               driver_backend_initialized=backend_initialized(),
               repeat_agreed_tokens=agreed, tensor_parallel=tensor_parallel)
    return out


# ---------------------------------------------------------------------------
# Phase: repeat — the exact prefix-cache hit, taken apart on the engine
# ---------------------------------------------------------------------------

def phase_repeat(sz: dict, rehearsal: bool, tensor_parallel: int = 1) -> dict:
    """An exact prefix-cache hit skips prefill: the prompt's pages are copied
    from the cache and the decode program re-derives position P-1. In bf16
    that is other arithmetic than prefill's, so the greedy continuation need
    not equal the cold send's token for token. This phase tells rounding
    from wrong data, on LLMEngines with the serve phase's configuration and
    seeded weights. The prompt is the serve phase's first, its last token
    replaced by the model's own greedy choice t, so that one engine can
    reach position P-1 the ordinary way: it prefills the P-1 tokens before,
    samples t, and decodes on. Asserted:

    - the hit IS that ordinary decode: its tokens equal, one for one, what
      follows t there (same bytes at positions 0..P-2, same program at P-1);
    - the copy: every layer's K/V at positions 0..P-2 of the hit's own pages
      are bit-identical to the cached pages, and those are left untouched;
    - position P-1: the K/V the decode program re-derived agree with
      prefill's within REDERIVED_TOL of that vector's largest magnitude,
      layer by layer;
    - every token of the cold, hit and decoded continuations is a greedy
      choice up to rounding: models.forward, teacher-forced in f32, gives
      each position's logits, the same in bf16 gives the size of bf16's
      error in them, and the chosen token's f32 logit is within twice that
      error of the best (an argmax over logits each off by at most it).

    Where cold and hit part, the two candidates' logits are printed. Returns
    t and the three continuations for the serve phase to meet over HTTP.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.accel.device import device_report, enable_compile_cache
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models.transformer import TransformerConfig, forward

    cache_dir = enable_compile_cache()
    out = {**device_report(), "compile_cache_dir": cache_dir}
    _require_platform(out, rehearsal)
    cfg = TransformerConfig(**sz["model"])
    ec = EngineConfig(**dict(sz["engine"], tensor_parallel=tensor_parallel))
    base, new = _prompts(sz)[0][0][:-1], sz["new_tokens"]
    P, ps = len(base) + 1, ec.page_size
    n_pg = -(-P // ps)
    failures = []

    def generate(eng, req_id, tokens, max_tokens, after_first_step=None) -> list:
        eng.add_request(req_id, tokens, max_tokens)
        while True:
            ev = eng.step().get(req_id, {})
            if after_first_step:
                after_first_step()
                after_first_step = None
            if ev.get("finished"):
                return ev["tokens"]

    t0 = time.perf_counter()
    ordinary = LLMEngine(cfg, engine_config=ec)
    t, *decoded = generate(ordinary, "decoded", base, new + 1)
    prompt = base + [t]
    eng = LLMEngine(cfg, params=ordinary.params, engine_config=ec)

    def kv_of(pages) -> np.ndarray:
        """[2, L, KV, P, Hd]: K and V at positions 0..P-1 of a page run."""
        rows = jnp.asarray(np.concatenate(
            [np.arange(pg * ps, (pg + 1) * ps) for pg in pages[:n_pg]])[:P])
        return np.stack([np.asarray(pool[:, :, rows, :].astype(jnp.float32))
                         for pool in eng.cache])

    cold = generate(eng, "cold", prompt, new)
    entry = eng._prefix_cache[eng._prefix_digests(prompt)[-1][1]]
    cached = kv_of(entry["pages"])
    mine = {}

    def snapshot_hit():  # admitted, pages copied, the first decode block ran
        slot = next(s_ for s_ in eng.slots if s_ is not None and s_.req_id == "hit")
        mine["kv"] = kv_of(slot.pages)

    hit = generate(eng, "hit", prompt, new, snapshot_hit)
    out["compile_s"] = round(time.perf_counter() - t0, 2)  # three runs, compiles included

    if eng.prefix_cache_stats["hits"] != 1:
        failures.append(f"the repeat was not an exact hit: {eng.prefix_cache_stats}")
    same = next((i for i, (x, y) in enumerate(zip(hit, decoded)) if x != y), new)
    say(f"  exact hit vs the engine that decoded its way to position P-1: "
        f"{same} of {new} tokens equal")
    if same < new:
        failures.append(f"the hit parts from the ordinary decode at token {same}: "
                        f"{hit[same:same + 2]} vs {decoded[same:same + 2]}")
    if not np.array_equal(kv_of(entry["pages"]), cached):
        failures.append("the hit wrote into the cached pages")
    if not np.array_equal(mine["kv"][..., : P - 1, :], cached[..., : P - 1, :]):
        failures.append("copied K/V at positions 0..P-2 are not bit-identical to the cache")
    a, b = mine["kv"][..., P - 1, :], cached[..., P - 1, :]  # [2, L, KV, Hd]
    rel = np.abs(a - b).max(axis=(2, 3)) / np.abs(b).max(axis=(2, 3))  # [2, L]
    out["kv_last_rel_err"] = {name: [round(float(x), 5) for x in row]
                              for name, row in zip("kv", rel)}
    say(f"  K/V at position P-1, decode program vs prefill, max|err| / max|value| by layer "
        f"(bound {REDERIVED_TOL:.4g}; {int((a == b).sum())} of {a.size} values bit-identical):")
    say(f"    K {out['kv_last_rel_err']['k']}")
    say(f"    V {out['kv_last_rel_err']['v']}")
    if not (np.isfinite(rel).all() and rel.max() <= REDERIVED_TOL):
        failures.append(f"re-derived K/V at P-1 off by {rel.max():.4g} of its magnitude")

    seqs = {"cold": cold, "hit": hit, "decoded": decoded}
    uniq = sorted({tuple(v) for v in seqs.values()})
    toks = jnp.asarray([prompt + list(u) for u in uniq], jnp.int32)

    def logits(cfg_):  # [n, new, vocab]: row j predicts continuation token j
        fn = jax.jit(lambda p_, t_: forward(p_, t_, cfg_)[0][:, P - 1: P - 1 + new])
        return np.asarray(fn(eng.params, toks).astype(jnp.float32))

    with jax.default_matmul_precision("highest"):
        l32 = logits(dataclasses.replace(cfg, dtype=jnp.float32, attention_impl="reference"))
    l16 = logits(dataclasses.replace(cfg, attention_impl="reference"))
    noise = float(np.abs(l32 - l16).max())
    chosen = np.take_along_axis(l32, np.asarray(uniq)[..., None], axis=-1)[..., 0]
    slack = l32.max(-1) - chosen  # [n, new] >= 0
    say(f"  bf16's error in a logit (forward in bf16 vs f32, max over {l32.size} logits): "
        f"{noise:.4g}; a greedy choice may trail the f32 best by {2 * noise:.4g}")
    for name, seq in seqs.items():
        row = slack[uniq.index(tuple(seq))]
        ok = bool(row.max() <= 2 * noise)
        say(f"  {'ok  ' if ok else 'FAIL'} {name}: trails the f32 best by {row.max():.4g} at most "
            f"(token {int(row.argmax())}); {int((row > 0).sum())} of {new} are not the f32 argmax")
        if not ok:
            failures.append(f"{name}: token {int(row.argmax())} trails the best logit by "
                            f"{row.max():.4g} > {2 * noise:.4g}")
    split = next((i for i, (x, y) in enumerate(zip(cold, hit)) if x != y), new)
    say(f"  exact hit vs the cold send: {split} of {new} tokens equal")
    if split < new:
        r = uniq.index(tuple(cold))
        x, y = cold[split], hit[split]
        say(f"    they part at token {split}: cold chose {x}, hit chose {y}; logits f32 "
            f"{l32[r, split, x]:.5f} vs {l32[r, split, y]:.5f} (best {l32[r, split].max():.5f}), "
            f"bf16 {l16[r, split, x]:.5f} vs {l16[r, split, y]:.5f}; margin "
            f"{abs(l32[r, split, x] - l32[r, split, y]):.4g} against an error of "
            f"{np.abs(l32[r, split] - l16[r, split]).max():.4g} at this position")
    out.update(failures=failures, logit_noise=noise, hit_equals_decoded=same == new,
               cold_hit_agree=split, worst_slack=float(slack.max()),
               tensor_parallel=tensor_parallel, last_token=t, tokens=seqs)
    return out


# ---------------------------------------------------------------------------
# Parent: one process a phase, nothing left behind
# ---------------------------------------------------------------------------

def _require_platform(report: dict, rehearsal: bool) -> None:
    if not rehearsal and report["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: the process that should hold the chip sees platform "
            f"{report['platform']!r} ({report['device_count']} x {report['device_kind']}); "
            f"use --rehearsal for a CPU run that proves nothing about the chip"
        )


def _run_phase(name: str, extra: list, deadline: float) -> dict:
    """Run one phase in its own session; echo its output; return its result.
    Whatever the phase started is gone when this returns."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name] + extra
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    result = None
    timer = signal.signal(signal.SIGALRM, lambda *_: os.killpg(proc.pid, signal.SIGKILL))
    signal.alarm(max(1, int(deadline - time.monotonic())))
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                sys.stdout.write(line)
                sys.stdout.flush()
        rc = proc.wait()
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, timer)
        stragglers = _session_pids(proc.pid)
        try:
            os.killpg(proc.pid, signal.SIGKILL)  # sweep anything it left
        except ProcessLookupError:
            pass
        # A killed process that held the chip takes seconds to release it
        # (longer with four chips than with one); the next phase, and
        # whoever runs after this script, needs it gone.
        t0 = time.monotonic()
        while (_session_pids(proc.pid) or not _chips_free()) and time.monotonic() - t0 < 120:
            time.sleep(0.1)
        waited = time.monotonic() - t0
    left = _session_pids(proc.pid)
    say(f"[{name}] processes the phase left running: {len(stragglers)}; after the sweep: "
        f"{len(left)} (waited {waited:.1f} s for the last thread to stop)")
    if rc != 0 or result is None or left:
        raise SystemExit(f"chip_smoke: phase {name} failed (exit code {rc}, processes left: {left})")
    say(f"[{name}] platform={result['platform']} device_kind={result['device_kind']!r} "
        f"devices={result['device_count']} jax={result['jax']} "
        f"compile_s={result['compile_s']} compile_cache={result['compile_cache_dir']}")
    if result["failures"]:
        raise SystemExit(f"chip_smoke: phase {name} failed: {result['failures']}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--phase", choices=("kernels", "train", "repeat", "serve"))
    ap.add_argument("--chips", type=int, default=0)
    ap.add_argument("--tensor-parallel", type=int, default=1)
    ap.add_argument("--expect", type=json.loads, help=argparse.SUPPRESS)
    args = ap.parse_args()
    sz = REHEARSAL if args.rehearsal else CHIP

    if args.phase:  # a child: run the phase, print its result, leave
        r, tp = args.rehearsal, args.tensor_parallel
        result = {
            "kernels": lambda: phase_kernels(sz, r),
            "train": lambda: phase_train(sz, r, args.chips),
            "repeat": lambda: phase_repeat(sz, r, tp),
            "serve": lambda: phase_serve(sz, r, args.chips, args.expect, tp),
        }[args.phase]()
        say(RESULT_TAG + json.dumps(result))
        return

    mode = ["--rehearsal"] if args.rehearsal else []
    if args.rehearsal:
        say("REHEARSAL: toy sizes on whatever backend JAX finds, kernels interpreted. "
            "Proves nothing about the chip.")
    deadline = time.monotonic() + DEADLINE_S
    kernels = _run_phase("kernels", mode, deadline)
    # The kernels phase counted the chips; this parent must not ask JAX.
    chips = ["--chips", str(0 if args.rehearsal else kernels["device_count"])]
    _run_phase("train", mode + chips, deadline)
    tp = ["--tensor-parallel", str(args.tensor_parallel)]
    repeat = _run_phase("repeat", mode + tp, deadline)
    expect = json.dumps({"last_token": repeat["last_token"], **repeat["tokens"]})
    serve_ = _run_phase("serve", mode + chips + tp + ["--expect", expect], deadline)
    say(f"serve replica ready in {serve_['ready_s']} s")
    if "jax" in sys.modules:
        raise SystemExit("chip_smoke: the parent imported jax")
    if args.rehearsal:
        say("rehearsal complete: no result")
        return
    say(json.dumps({"ok": True, "device": {
        "platform": kernels["platform"], "kind": kernels["device_kind"],
        "count": kernels["device_count"]}}))


if __name__ == "__main__":
    main()
