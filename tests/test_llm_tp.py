"""Tensor-parallel LLM serving: the engine sharded over a `tensor` mesh axis
(params Megatron-split, KV pools split by kv_heads) must produce byte-identical
greedy output to the single-device engine, whole-prompt and chunked prefill
alike, and a serve replica must gang-schedule onto a host advertising the TP
degree's chips.

Reference analogue: TP degree -> placement-group bundle mapping
(llm/_internal/serve/engines/vllm/vllm_models.py:233-238; vLLM executes the
sharded model — here the sharded execution is native, ray_tpu/llm/engine.py).
Runs on the virtual 8-device CPU mesh (conftest).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.models import TransformerConfig

CFG = TransformerConfig(
    vocab_size=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
)
PROMPT = [5, 17, 42, 7, 23, 11, 2]


ENGINE_KW = dict(max_slots=4, max_seq=128, prefill_buckets=(16, 32), page_size=32)


def _engine(tp: int, **ec_kw) -> LLMEngine:
    return LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW, tensor_parallel=tp, **ec_kw))


@pytest.mark.parametrize("chunked_prefill", [0, 32])
def test_tp_greedy_matches_single_device(chunked_prefill):
    """mesh=tensor(2) must not change greedy output vs one device — the
    round-5 acceptance bar for sharded serving. A 70-token prompt is one
    prefill of bucket 128, or with chunked_prefill three runs of the tail
    program (32 + 32 + 6 tokens over 0, 1 and 2 context pages)."""
    prompt = [(11 * i + 5) % 96 for i in range(70)]
    ref = _engine(1, chunked_prefill=chunked_prefill).generate(prompt, max_tokens=10)["tokens"]
    tp = _engine(2, chunked_prefill=chunked_prefill).generate(prompt, max_tokens=10)["tokens"]
    assert tp == ref, f"chunked_prefill={chunked_prefill}: TP output diverged: {tp} vs {ref}"


def test_tp_actually_shards_params_and_kv():
    eng = _engine(2)
    wq = eng.params["layers"]["wq"]  # [L, D, H, Hd]: heads sharded
    assert wq.addressable_shards[0].data.shape[2] == CFG.n_heads // 2
    mlp = eng.params["layers"]["w_gate"]  # [L, D, F]: ffn hidden sharded
    assert mlp.addressable_shards[0].data.shape[2] == CFG.d_ff // 2
    # Paged KV pool [L, KV, pages*ps, Hd]: kv_heads sharded.
    assert eng.cache[0].addressable_shards[0].data.shape[1] == CFG.kv_heads // 2


def test_tp_weight_handoff_through_object_store():
    """train->serve handoff of a TP-SHARDED param tree through the object
    store: every leaf ships one OOB buffer per unique shard (no host gather
    — core/serialization.py sharded transport), and an engine constructed
    from the fetched tree serves byte-identical greedy output."""
    import ray_tpu as rt

    src = _engine(2)
    ref_out = src.generate(PROMPT, max_tokens=10)["tokens"]
    wq = src.params["layers"]["wq"]
    assert len(wq.sharding.device_set) == 2  # really sharded going in

    rt.init(num_cpus=2)
    try:
        ref = rt.put(src.params)
        fetched = rt.get(ref, timeout=120)
    finally:
        rt.shutdown()
    # Shards survived the hop: same per-device layout, no gather artifact.
    fq = fetched["layers"]["wq"]
    assert len(fq.sharding.device_set) == 2
    assert fq.addressable_shards[0].data.shape == wq.addressable_shards[0].data.shape
    served = LLMEngine(CFG, params=fetched, engine_config=EngineConfig(
        **ENGINE_KW, tensor_parallel=2))
    assert served.generate(PROMPT, max_tokens=10)["tokens"] == ref_out


def test_tp_params_ref_served_through_deployment():
    """The wired train->serve path: build_llm_app(params=ObjectRef) — the
    REPLICA (a separate worker process) fetches the sharded tree from the
    object store and serves it, output matching the source engine."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    src = _engine(2)
    ref_out = src.generate(PROMPT, max_tokens=8)["tokens"]

    rt.init(num_cpus=8, resources={"TPU": 2.0})
    try:
        serve.start(proxy=False)
        ref = rt.put(src.params)
        app = build_llm_app(
            model_config=dict(
                vocab_size=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                d_ff=128, max_seq_len=128, attention_impl="reference",
            ),
            engine_config={**ENGINE_KW, "tensor_parallel": 2},
            params=ref,
            # Zero chips: this replica shards over the virtual CPU devices.
            # One scheduled onto TPU resources would refuse to run on them
            # (test_tp_replica_on_cpu_host_raises).
            ray_actor_options={"resources": {"TPU": 0.0}},
        )
        serve.run(app, name="tp-handoff", http=False)
        h = serve.get_deployment_handle("llm", "tp-handoff")
        out = h.generate.remote(PROMPT, 8).result(timeout=300)
        assert out["tokens"] == ref_out, (out["tokens"], ref_out)
        serve.delete("tp-handoff")
    finally:
        serve.shutdown()
        rt.shutdown()


def test_tp_rejects_indivisible_model():
    with pytest.raises(ValueError, match="not divisible"):
        _engine(4)  # kv_heads=2 % 4 != 0


def test_tp_mixed_batch_and_sampling():
    """Continuous batching under TP: concurrent requests with different
    per-request sampling params behave like the single-device engine."""
    from ray_tpu.llm.sampling import SamplingParams

    eng = _engine(2)
    eng.add_request("greedy", PROMPT, 8,
                    sampling=SamplingParams(temperature=0.0, max_tokens=8))
    eng.add_request("hot", list(reversed(PROMPT)), 8,
                    sampling=SamplingParams(temperature=0.9, top_k=20, max_tokens=8))
    done = {}
    while eng.has_work():
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
    ref = _engine(1).generate(
        PROMPT, 8, sampling=SamplingParams(temperature=0.0, max_tokens=8)
    )["tokens"]
    assert done["greedy"] == ref
    assert len(done["hot"]) == 8


def test_tp_prefix_cache_hit_correct():
    """Prefix-cache page copy works on a kv_heads-sharded pool (the copy
    slices the token axis; the sharded axis rides along)."""
    eng = _engine(2, prefix_cache=True, temperature=0.0)
    cold = eng.generate(PROMPT, max_tokens=8)["tokens"]
    warm = eng.generate(PROMPT, max_tokens=8)["tokens"]
    assert eng.prefix_cache_stats["hits"] == 1
    assert warm == cold


def test_tp_replica_on_cpu_host_raises():
    """A TP-2 replica declares {"TPU": 2} (build_llm_app). Scheduled onto
    those resources on a host whose devices are CPUs, it refuses to come up
    rather than serve from the CPU under a TPU's name."""
    import ray_tpu as rt
    from ray_tpu.llm.deployment import LLMServer

    rt.init(num_cpus=8, resources={"TPU": 2.0})
    try:
        replica = rt.remote(LLMServer).options(resources={"TPU": 2.0}).remote(
            dict(vocab_size=96, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                 d_ff=128, max_seq_len=128, attention_impl="reference"),
            {"max_slots": 4, "max_seq": 128, "prefill_buckets": (16, 32),
             "tensor_parallel": 2},
        )
        with pytest.raises(Exception, match="scheduled onto 2 TPU chip.*platform 'cpu'"):
            rt.get(replica.check_health.remote(), timeout=300)
    finally:
        rt.shutdown()


def test_tp_a_block_ahead_staggered_requests_and_reused_slots_match_their_solo_runs():
    """The look-ahead engine under a mesh: a step enqueues its decode block
    before it fetches the one before, the mirrors stay replicated whoever
    wrote them last, and 6 requests through 2 of its slots (blocks of 8, an EOS
    inside a block) return what each gives alone on one device."""
    from ray_tpu.accel import device

    prompts = [[(7 * i + 3 * j + 1) % 96 for j in range(4 + 3 * i)] for i in range(6)]
    one = _engine(1)
    solos = [one.generate(p, max_tokens=18)["tokens"] for p in prompts]
    eos = solos[1][4]  # inside the first block of 8
    want = [s[: s.index(eos) + 1] if eos in s else s for s in solos]
    eng = LLMEngine(CFG, engine_config=EngineConfig(
        **{**ENGINE_KW, "max_slots": 2}, tensor_parallel=2, eos_id=eos))
    device._count_compiles()
    before = device.compile_events()["count"]
    eng.warmup()
    compiled = device.compile_events()["count"]
    assert compiled > before
    done = {}
    pending = list(enumerate(prompts))
    while pending or eng.has_work():
        if pending:
            i, p = pending.pop(0)
            eng.add_request(f"r{i}", p, 18)
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
    assert [done[f"r{i}"] for i in range(6)] == want
    steps = eng.trace_snapshot()["steps"]
    assert sum(s["ahead"] for s in steps) > 0 and sum(s["dropped_rows"] for s in steps) > 0
    # a retire's per-row write and every program that met its result were warmed
    assert device.compile_events()["count"] == compiled
    for mirror in (eng.d_lengths, eng.d_last, eng.d_page_tables, eng.d_temps):
        assert mirror.sharding.is_equivalent_to(eng._replicated, mirror.ndim)


def test_a_sharded_replicas_weights_are_one_program_for_every_seed(monkeypatch):
    """The key is the program's ARGUMENT (PR 58): on four devices two seeds
    lower the same text, so the compile cache holds one executable for every
    seed and not one a seed, compiled anew at each start; and the weights are
    leaf for leaf, bit for bit, those of the program with the seed inside
    (the engine's line before), which are ``init_params`` of the seed's key
    (to a rounding: a fused multiply-add of the compiled form)."""
    import dataclasses

    import jax

    from ray_tpu.models.transformer import init_params

    cfg = dataclasses.replace(CFG, n_kv_heads=4)
    jit, texts = jax.jit, []

    def spying(fun, **kw):
        jitted = jit(fun, **kw)
        if not isinstance(kw.get("out_shardings"), dict):  # the weights' tree of shardings
            return jitted

        def lowered_and_called(*args):
            texts.append(jitted.lower(*args).as_text())
            return jitted(*args)
        return lowered_and_called

    monkeypatch.setattr(jax, "jit", spying)
    engines = {seed: LLMEngine(cfg, engine_config=EngineConfig(**ENGINE_KW, tensor_parallel=4, seed=seed))
               for seed in (0, 20250601)}
    monkeypatch.undo()
    assert len(texts) == 2 and texts[0] == texts[1]
    for seed, eng in engines.items():
        assert len(eng.params["layers"]["wq"].sharding.device_set) == 4
        before = jax.jit(lambda: init_params(jax.random.PRNGKey(seed), cfg), out_shardings=eng._param_shardings)()
        same = jax.tree.map(lambda a, b: a.dtype == b.dtype and bool((np.asarray(a) == np.asarray(b)).all()),
                            eng.params, before)
        assert all(jax.tree.leaves(same)), same
        jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
                     eng.params, init_params(jax.random.PRNGKey(seed), cfg))
    a, b = (np.asarray(eng.params["layers"]["wq"]) for eng in engines.values())
    assert (a != b).any()  # and the seeds' weights are not each other's
