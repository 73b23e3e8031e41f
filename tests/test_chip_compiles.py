"""The paged decode call compiled by the TPU's own compiler, for a v5e that is
described and not attached (no chip, no chip time): what interpret mode cannot
show. Mosaic has to lower a grid whose length is a runtime value, copies of
scattered pages out of pools that stay in HBM and are aliased to the outputs,
a buffer of several pages a grid step and one of a single page (the same
kernel), at the shapes a chip holds in the serve cells and at a head size
under a lane tile.
A compile that passes is not a chip run and says nothing about results or
speed; tests/test_paged_attention.py holds the results, chip_smoke.py the chip.

The topology is described inside a fixture and never at import: one process
at a time may hold the TPU's library, and every xdist worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import paged_attention as pa

SHAPES = {
    # a chip's share: B, H, KV, D, page_size, pages a sequence, layers, pool pages
    "internlm2-1.8b": (32, 16, 8, 128, 128, 16, 24, 384),
    "mistral-7b-v0.3, a chip of four": (32, 8, 2, 128, 128, 32, 32, 1088),
    "head size 64 (chip_smoke's model)": (32, 16, 4, 64, 128, 16, 12, 96),
    "laguna-s-2.1-ep8's full layers, a group of 6": (64, 48, 8, 128, 128, 73, 3, 3072),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    and cannot be read back without the device: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _call_with_group(group, window=0):
    """The paged call on the walk it builds itself (group None: the page
    group it reads off its shapes) or on a walk of `group` pages a step."""
    def call(q, k_new, v_new, k_pages, v_pages, lengths, table, layer):
        walk = None if group is None else pa.page_groups(lengths, table, k_pages.shape[3], window, group)
        return pa.paged_attention(q, k_new, v_new, k_pages, v_pages, lengths, table, layer, walk=walk, window=window)
    return call


@pytest.mark.parametrize("group", [None, 1], ids=["its_own_group", "a_page_a_step"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_paged_call_lowers_for_a_v5e(shape, group, one_chip, no_compile_cache, monkeypatch):
    B, H, KV, D, ps, n_pages, L, P_total = SHAPES[shape]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the call asks before it lowers

    def arr(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    # a head under a lane tile lies in rows of a whole one from allocation (kv_row_width), q and the token's rows
    # alone are padded by the call; a pool as narrow as such a head is refused
    pool = arr((L, KV, P_total, ps, pa.kv_row_width(D)), jnp.bfloat16)
    args = (arr((B, H, D), jnp.bfloat16), arr((B, KV, D), jnp.bfloat16), arr((B, KV, D), jnp.bfloat16),
            pool, pool, arr((B,), jnp.int32), arr((B, n_pages), jnp.int32), arr((), jnp.int32))
    compiled = jax.jit(_call_with_group(group), donate_argnums=(3, 4)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1  # one Mosaic call, the walk beside it plain XLA
    # nothing but the call's operands: no copy of a pool (a pool is 0.1-2.4 GB here)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20
    if D % 128:
        narrow = arr((L, KV, P_total, ps, D), jnp.bfloat16)
        with pytest.raises(ValueError, match="allocate them kv_row_width"):
            jax.jit(_call_with_group(group)).lower(*args[:3], narrow, narrow, *args[5:])


@pytest.mark.parametrize("group", [None, 1], ids=["its_own_group", "a_page_a_step"])
def test_the_window_call_lowers_for_a_v5e(group, one_chip, no_compile_cache, monkeypatch):
    """The paged call with a window at the serve cell's shapes (64 slots, 72
    query heads over 8 KV heads: a group of 9, two sublane tiles; a window of
    512 over pages of 128: rings of 5 pages, 6 layers): one Mosaic call named
    for the trace, the ring pools aliased and not copied."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, H, KV, D, ps, n_pages, L, W = 64, 72, 8, 128, 128, 73, 6, 512

    def arr(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    pool = arr((L, KV, B * pa.ring_pages(W, ps), ps, D), jnp.bfloat16)
    args = (arr((B, H, D), jnp.bfloat16), arr((B, KV, D), jnp.bfloat16), arr((B, KV, D), jnp.bfloat16),
            pool, pool, arr((B,), jnp.int32), arr((B, n_pages), jnp.int32), arr((), jnp.int32))
    compiled = jax.jit(_call_with_group(group, W), donate_argnums=(3, 4)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "window_attn" in text and "paged_attn" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20  # a pool is 0.5 GB


def test_the_decode_program_of_a_model_with_window_layers_compiles_for_a_v5e(one_chip, no_compile_cache, monkeypatch):
    """llm/engine.py ``_decode_impl`` of a model with a layer pattern, at
    heads of 128 in groups of 6 and 9 and otherwise small widths: the period
    scan around the two paged calls' kinds and the grouped matmul. 1 + 4
    layers are one paged call in the dense stack, one and three window calls
    in the period, and three grouped matmuls in each of its four layers; no
    pool is copied (the two kinds' four pools are 27 MB, the program's
    temporaries a fraction), and a layer's parameters are its slice of its
    kind's stack, not a period's slice of it."""
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models.transformer import LayerKind, TransformerConfig, init_params

    full = LayerKind("full_attention", 12, rope_theta=5e5, rope_share=0.5, yarn_factor=128.0, yarn_original_len=8192,
                     attention_factor=1.4852030263919618)
    sliding = LayerKind("sliding_attention", 18, window=512)
    cfg = TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=5, n_heads=12, n_kv_heads=2, head_dim=128, d_ff=512, max_seq_len=2048,
        param_dtype=jnp.bfloat16, layer_pattern=(full, sliding, sliding, sliding), attn_gate="per_head",
        n_dense_layers=1, n_experts=32, expert_top_k=4, experts_held=8, expert_d_ff=256, n_shared_experts=1,
        routed_scaling=2.5, router_score="sigmoid")

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(
        max_slots=8, max_seq=2048, page_size=128, total_pages=40, prefill_buckets=(512,), decode_block=8))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks which attend to trace
    B = eng.ec.max_slots
    ints, floats = on_chip(jnp.zeros(B, jnp.int32)), on_chip(jnp.zeros(B, jnp.float32))
    compiled = eng._decode_jit.lower(
        params, tuple(on_chip(pool) for pool in eng.cache), ints, ints, on_chip(eng.d_page_tables),
        on_chip(jax.random.PRNGKey(0)), 8, floats, floats, ints).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 + 3 + 3 * 4
    assert sum(pool.nbytes for pool in eng.cache) == 2 * (2 * 2 * 40 * 128 * 128 * 2) + 2 * (3 * 2 * 8 * 5 * 128 * 128 * 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


def test_the_latent_paged_call_lowers_for_a_v5e(one_chip, no_compile_cache, monkeypatch):
    """ops/latent_attention.py at the serve cell's shapes (128 slots, 128
    heads, rows of 640 = 512 + 64 padded to lane tiles, 5 layers, 3,584 pages):
    one Mosaic call, the pool aliased and not copied."""
    from ray_tpu.ops import latent_attention as la

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, H, W, R, ps, n_pages, L, P_total = 128, 128, 640, 512, 128, 32, 5, 3584

    def arr(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(q, row, pool, lengths, table, layer):
        return la.latent_paged_attention(q, row, pool, lengths, table, layer, v_width=R, scale=192 ** -0.5)

    args = (arr((B, H, W), jnp.bfloat16), arr((B, W), jnp.bfloat16), arr((L, P_total, ps, W), jnp.bfloat16),
            arr((B,), jnp.int32), arr((B, n_pages), jnp.int32), arr((), jnp.int32))
    compiled = jax.jit(call, donate_argnums=(2,)).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20  # the pool is 2.9 GB


@pytest.mark.parametrize("tokens,tm,K,N", [(128, 16, 7680, 2048), (128, 16, 2048, 7680), (2048, 128, 7680, 2048)])
def test_the_grouped_matmul_lowers_for_a_v5e(tokens, tm, K, N, one_chip, no_compile_cache, monkeypatch):
    """ops/grouped_matmul.py at the serve cell's shapes: a decode step's and a
    2048-token prompt's pairs over 16 held experts of 4 layers, the whole
    stack an operand (a layer's slice of it would be a 0.5 GB copy a call)."""
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    M = gm.plan_rows(tokens * 8, 16, tm)

    def arr(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(x, w, layer, tile_expert, n_tiles):
        return gm.expert_gmm(x, w, layer, tile_expert, n_tiles, tm=tm)

    compiled = jax.jit(call).lower(arr((M, K), jnp.bfloat16), arr((4, 16, K, N), jnp.bfloat16), arr((), jnp.int32),
                                   arr((M // tm,), jnp.int32), arr((1,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20  # the stack is 2.0 GB


@pytest.mark.parametrize("rows,vocab", [(32, 92544), (128, 19200)])
def test_the_samplers_conditionals_compile_for_a_v5e(rows, vocab, one_chip, no_compile_cache):
    """llm/sampling.py ``sample_batch`` under a scan of decode steps at the
    serve cells' batch and vocabulary: the TPU compiler keeps both
    conditionals (it makes neither a select), and the top-k and the random
    bits lie in their branches, so an all-greedy step runs neither."""
    from ray_tpu.llm.sampling import sample_batch

    def arr(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def steps(logits, temps, top_ps, top_ks, key):
        def one_step(last, step_key):
            with jax.named_scope("sample"):
                toks = sample_batch(logits + last[:, None].astype(jnp.float32), temps, top_ps, top_ks, step_key)
            return toks, toks
        return jax.lax.scan(one_step, jnp.zeros(rows, jnp.int32), jax.random.split(key, 8))

    text = jax.jit(steps).lower(arr((rows, vocab), jnp.float32), arr((rows,), jnp.float32), arr((rows,), jnp.float32),
                                arr((rows,), jnp.int32), arr((2,), jnp.uint32)).compile().as_text()
    assert text.count(" conditional(") == 2
    costly = [line for line in text.splitlines() if "sample/" in line and ("/top_k" in line or "_gumbel" in line)]
    assert costly and all("sample/cond/" in line for line in costly)


def test_the_delta_rules_two_calls_lower_for_a_v5e(one_chip, no_compile_cache, monkeypatch):
    """ops/linear_attention.py at the serve cell's shapes. ``kda_step``: 128
    slots of 64 heads of 128 x 128 float32, 3 layers' states in one pool of
    1.6 GB that is aliased and not copied, the grid a runtime value.
    ``kda_chunk``: one prompt of 2,048 and of 8,192 positions (the cell's
    largest bucket) of 64 heads, the operands in the mixer's dtypes (q, k, g
    float32, v bfloat16): blocks of [64, 8 heads, 128] turned by head inside,
    float32 products at the highest precision a head, a [128, 64] operand
    contracted over its rows; and of 12, 3 and 8 heads, where a block spans
    every head. The call reads and writes the arrays where they lie: a float32
    copy of ONE operand by head (67 MB at 2,048 positions; PR 44's call made
    five) would be more than all it may hold beside them."""
    from ray_tpu.ops import linear_attention as la

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, H, K, L = 128, 64, 128, 3

    def arr(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    row = arr((B, H, K))
    compiled = jax.jit(la.kda_step, donate_argnums=(5,)).lower(
        row, row, row, row, arr((B, H)), arr((L, B, H, K, K)), arr((), jnp.int32), arr((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "kda_step" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 40 << 20  # the rows' operands, a tile a head: 34 MB

    # 12 and 3 heads are no whole tiles of 8: the block spans every head. 8 heads: v's block is half a bfloat16 tile.
    for S, H in ((2048, 64), (8192, 64), (512, 12), (512, 3), (512, 8)):
        seq = arr((1, S, H, K))
        compiled = jax.jit(lambda q, k, v, g, beta: la.kda_chunk(q, k, v, g, beta, out_dtype=jnp.bfloat16)).lower(
            seq, seq, arr((1, S, H, K), jnp.bfloat16), seq, arr((1, S, H))).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and "kda_chunk" in text
        assert compiled.memory_analysis().temp_size_in_bytes < S * H * K * 4  # not one operand by head in float32


@pytest.mark.parametrize("cell,window,taps,key_heads", [
    ("solar-open2-250b-ep8, bucket 8192", (1, 8195, 3, 64, 128), (4, 3, 64, 128), 64),
    ("gigachat3.5-432b-ep16, bucket 2048", (1, 2051, 128, 128), (4, 128, 128), 32),
    ("12 heads: no whole tiles of 16", (1, 515, 3, 12, 128), (4, 3, 12, 128), 12),
    ("4 key heads for 8 heads, a prompt under a block", (1, 27, 16, 128), (4, 16, 128), 4),
])
def test_delta_prep_lowers_for_a_v5e(cell, window, taps, key_heads, one_chip, no_compile_cache, monkeypatch):
    """ops/linear_attention.py ``delta_prep`` at both serve cells' head counts
    and largest buckets (three stacked sets of 64 heads; 32 + 32 + 64 heads
    along one axis) and at head counts that are no whole bfloat16 tiles: one
    Mosaic call named for the trace, which reads the window where it lies and
    holds nothing beside its operands and results, and whose body (what a
    warm start lowers anew, ROADMAP S12) is one small loop."""
    from ray_tpu.ops import linear_attention as la

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def arr(dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    traced = jax.jit(lambda w, t: la.delta_prep(w, t, key_heads)).trace(arr(window), arr(taps))
    (call,) = [eqn for eqn in traced.jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    assert len(call.params["jaxpr"].eqns) <= 12  # the rows' two copies and the loop; tests/test_linear_attention.py counts inside it
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "delta_prep" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20  # the window is 25-403 MB at the cells' shapes


_HLO_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "s8": 1, "u8": 1}


def _entry_instructions(text):
    """{name: (opcode, result bytes, operand names, op_name)} of a compiled
    module's entry computation, read off its text: a result's bytes are its
    type's (a tuple's: every part's), operands are the distinct ``%names``
    inside the instruction's brackets."""
    import math
    import re

    def nbytes(types):
        found = re.findall(r"\b(" + "|".join(_HLO_BYTES) + r")\[([\d,]*)\]", types)
        return sum(_HLO_BYTES[t] * math.prod(int(d) for d in dims.split(",") if d) for t, dims in found)

    lines = text[text.index("\nENTRY "):].splitlines()[2:]
    out = {}
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z0-9\-]*)\((.*)$", line)
        if not m:
            continue
        name, result, opcode, rest = m.groups()
        depth, end = 1, len(rest)
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if not depth:
                end = i
                break
        scope = re.search(r'op_name="([^"]*)"', line)
        out[name] = (opcode, nbytes(result), list(dict.fromkeys(re.findall(r"%([\w.\-]+)", rest[:end]))),
                     scope.group(1) if scope else "")
    return out


def test_solars_prefill_program_prepares_a_delta_layers_q_k_v_in_one_pass_for_a_v5e(one_chip, no_compile_cache, monkeypatch):
    """llm/engine.py's prefill program of bucket 4096 at
    benchmarks/configs/solar-open2-250b-ep8.json's published widths (one
    softmax layer and three delta layers of 64 heads of 128; small pools: the
    program between a delta layer's projections and its rule does not see
    them), compiled as the cell's replica compiles it. What moves between the
    three projections and ``kda_chunk``, counted as operand plus result bytes
    of the entry computation's instructions (the request's scan and the
    period's are flattened into it) under ``short_conv/`` (the new call among
    them; an array it is handed twice counted once), ``qkv/broadcast_in_dim``
    (q~, k~ and v~ turned from the products' heads-first layout to positions
    first: three copies), ``qkv/concatenate`` (the window) and the decay's
    ``kda_chunk/jit(_where)/select_n``: 1.54 GB a delta layer as read here
    (0.40 the copies, 0.40 the window, 0.54 ``delta_prep``, 0.20 the decay),
    where the jax.numpy lines moved 2.76 (the convolution's result in float32
    written once and read twice more: 0.60 + 0.41 + 0.74). One ``delta_prep``
    a delta layer, beside the three ``kda_chunk`` and the flash call."""
    import importlib.util
    import json
    import os
    import re

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models.transformer import TransformerConfig, init_params

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    spec = importlib.util.spec_from_file_location("bench_solar_open2", os.path.join(bench, "architectures", "solar_open2.py"))
    arch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arch)
    with open(os.path.join(bench, "configs", "solar-open2-250b-ep8.json")) as f:
        model = json.load(f)
    cfg = TransformerConfig(**{**arch.transformer_kwargs(model), "param_dtype": jnp.bfloat16})
    assert (cfg.d_model, cfg.n_layers, cfg.head_dim) == (4096, 4, 128) and [k.n_heads for k in cfg.kinds] == [64, 64]

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the engine asks how wide a pool's rows are, the mixer which lines to trace
    bucket = 4096
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(
        max_slots=8, max_seq=bucket + 128, page_size=128, total_pages=68, prefill_buckets=(bucket,), decode_block=8))
    i32 = lambda *dims: on_chip(jnp.zeros(dims, jnp.int32))
    f32 = lambda *dims: on_chip(jnp.zeros(dims, jnp.float32))
    compiled = eng._prefill(bucket, 1).lower(
        params, tuple(on_chip(pool) for pool in eng.cache), i32(1, bucket), i32(1), i32(1, bucket // 128),
        on_chip(jax.random.PRNGKey(0)), f32(1), f32(1), i32(1), i32(1)).compile()
    text = compiled.as_text()
    instructions = _entry_instructions(text)
    calls = {kernel: sorted(n for n, (opcode, *_) in instructions.items() if opcode == "custom-call" and n.startswith(kernel))
             for kernel in ("delta_prep", "kda_chunk", "flash_attn_fwd")}
    assert [len(calls[k]) for k in ("delta_prep", "kda_chunk", "flash_attn_fwd")] == [3, 3, 1], calls
    between = re.compile(r"/short_conv/|/qkv/(concatenate|broadcast_in_dim)$|/kda_chunk/jit\(_where\)/select_n$")
    moved = {name: result + sum(instructions[o][1] for o in operands if o in instructions)
             for name, (opcode, result, operands, scope) in instructions.items()
             if between.search(scope) and opcode not in ("get-tuple-element", "bitcast", "constant", "parameter")}
    a_layer = sum(moved.values()) / 3
    assert all(call in moved for call in calls["delta_prep"])
    assert 1.2e9 < a_layer <= 1.6e9, (a_layer, sorted(moved.items(), key=lambda kv: -kv[1])[:12])


def test_the_decode_program_of_a_model_with_delta_layers_compiles_for_a_v5e(one_chip, no_compile_cache, monkeypatch):
    """llm/engine.py ``_decode_impl`` of a model with one softmax layer and
    three delta layers a period, at heads of 128 and otherwise small widths:
    the period scan around one paged call, three ``kda_step`` calls and three
    grouped matmuls a layer; the state pool (4 MB a slot) and the page pools
    are carried and aliased, and no copy of either is among the temporaries."""
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models.transformer import LayerKind, TransformerConfig, init_params

    gqa = LayerKind("gqa", 8, rope_share=0.0)
    kda = LayerKind("kda", 8, mixer="delta", conv_size=4, low_rank=128, beta_scale=2.0)
    cfg = TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=4, n_heads=8, n_kv_heads=2, head_dim=128, d_ff=512, max_seq_len=2048,
        param_dtype=jnp.bfloat16, layer_pattern=(gqa, kda, kda, kda), attn_gate="elementwise", norm_eps=1e-5,
        n_experts=32, expert_top_k=4, experts_held=8, expert_d_ff=256, n_shared_experts=1, router_score="sigmoid")

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(
        max_slots=16, max_seq=2048, page_size=128, total_pages=40, prefill_buckets=(512,), decode_block=8))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks which attend to trace
    B = eng.ec.max_slots
    ints, floats = on_chip(jnp.zeros(B, jnp.int32)), on_chip(jnp.zeros(B, jnp.float32))
    compiled = eng._decode_jit.lower(
        params, tuple(on_chip(pool) for pool in eng.cache), ints, ints, on_chip(eng.d_page_tables),
        on_chip(jax.random.PRNGKey(0)), 8, floats, floats, ints).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + 3 + 3 * 4
    state, tails = eng.cache[2:]
    assert state.shape == (3, 16, 8, 128, 128) and state.dtype == jnp.float32 and state.nbytes == 25_165_824
    assert compiled.memory_analysis().temp_size_in_bytes < state.nbytes // 3  # not one layer's states


def test_the_decode_program_of_a_model_with_a_latent_layer_beside_delta_layers_compiles_for_a_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """llm/engine.py ``_decode_impl`` of a model with a leading dense delta
    layer and a period of one latent layer and three delta layers (half as many
    key heads as value heads, a decay a head), at heads of 128, latent rows of
    512 + 64 and otherwise small widths: the dense stack's scan around one
    ``kda_step``, the period scan around one ``latent_attn`` call, three
    ``kda_step`` calls and three grouped matmuls a layer; both rules' pools (a
    state of 4 MB a slot and layer, latent rows in pages) are carried and
    aliased, and no copy of either is among the temporaries."""
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models.transformer import LayerKind, TransformerConfig, init_params

    latent = LayerKind("latent", 8, mixer="latent", rope_theta=1e5, yarn_factor=8.0, yarn_original_len=32768,
                       softmax_factor=1.459)
    delta = LayerKind("delta", 8, mixer="delta", conv_size=4, n_key_heads=4, gate_scale=2.0)
    cfg = TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=5, n_heads=8, head_dim=128, d_ff=512, max_seq_len=2048,
        param_dtype=jnp.bfloat16, layer_pattern=(delta, latent, delta, delta), n_dense_layers=1,
        attn_gate="elementwise", sandwich_norm=True, norm_gating=2.0, swiglu_limit=10.0,
        q_lora_rank=192, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        n_experts=32, expert_top_k=4, experts_held=8, expert_d_ff=256, n_shared_experts=1, routed_scaling=2.5,
        router_score="sigmoid")

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(
        max_slots=16, max_seq=2048, page_size=128, total_pages=40, prefill_buckets=(512,), decode_block=8))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the program asks which attend to trace
    B = eng.ec.max_slots
    ints, floats = on_chip(jnp.zeros(B, jnp.int32)), on_chip(jnp.zeros(B, jnp.float32))
    compiled = eng._decode_jit.lower(
        params, tuple(on_chip(pool) for pool in eng.cache), ints, ints, on_chip(eng.d_page_tables),
        on_chip(jax.random.PRNGKey(0)), 8, floats, floats, ints).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (1 + 3) + 1 + 3 * 4  # kda_step, latent_attn, expert_gmm
    state, tails, rows = eng.cache
    assert state.shape == (4, 16, 8, 128, 128) and state.dtype == jnp.float32 and state.nbytes == 33_554_432
    assert tails.shape == (4, 16, 3, 16, 128) and rows.shape == (1, 40 * 128, 640) and rows.dtype == jnp.bfloat16
    assert compiled.memory_analysis().temp_size_in_bytes < state.nbytes // 4  # not one layer's states


def test_the_state_space_rules_two_calls_lower_for_a_v5e(one_chip, no_compile_cache, monkeypatch):
    """ops/ssd.py at the serve cell's shapes. ``ssd_step``: 64 slots of 64
    heads of 64 columns and a state size of 128, 36 layers' states in one pool
    of 4.8 GB that is aliased and not copied, the grid a runtime value.
    ``ssd_chunk``: one prompt of 512 and of 1,536 positions (the cell's largest
    bucket), x, B and C in bfloat16 and the decays float32, 32 heads a grid
    step, two a lane tile; and of 8 heads of 128 columns, one a lane tile."""
    from ray_tpu.ops import ssd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, H, P, N, L = 64, 64, 64, 128, 36

    def arr(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    shared = arr((B, 1, N), jnp.bfloat16)
    compiled = jax.jit(ssd.ssd_step, donate_argnums=(5,)).lower(
        arr((B, H, P), jnp.bfloat16), shared, shared, arr((B, H)), arr((B, H)), arr((L, B, N, H * P)),
        arr((), jnp.int32), arr((B,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "ssd_step" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20  # the rows' operands: 2 MB each; a slot's state is 2 MB

    for S, H, P in ((512, 64, 64), (1536, 64, 64), (256, 8, 128)):
        compiled = jax.jit(ssd.ssd_chunk).lower(
            arr((1, S, H, P), jnp.bfloat16), arr((1, S, 1, N), jnp.bfloat16), arr((1, S, 1, N), jnp.bfloat16),
            arr((1, S, H)), arr((1, S, H))).compile()
        text = compiled.as_text()
        assert text.count("tpu_custom_call") == 1 and "ssd_chunk" in text
        assert compiled.memory_analysis().temp_size_in_bytes < S * H * P * 4  # not x by head in float32


def test_the_decode_program_of_a_model_with_state_space_layers_compiles_for_a_v5e(one_chip, no_compile_cache,
                                                                                  monkeypatch):
    """llm/engine.py ``_decode_impl`` of a model with four state-space layers
    around one softmax layer of 64-wide heads a period, two periods, at the
    serve cell's mixer widths and otherwise small ones: the period scan around
    one paged call and four ``ssd_step`` calls. The page pools' rows are a
    whole lane tile wide from allocation (ops ``kv_row_width`` asks the backend,
    which is steered before the engine is made), so the paged call takes them
    as they lie; the state pool (2 MB a layer a slot) and the page pools are
    carried and aliased, and no copy of either is among the temporaries; the
    input projection's stack is no operand of a copy (stored [outputs, D])."""
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models.transformer import LayerKind, TransformerConfig, init_params

    attention = LayerKind("attention", 32, rope_share=0.0)
    mamba = LayerKind("mamba", 64, mixer="ssd", conv_size=4, head_width=64, state_size=128, n_groups=1)
    cfg = TransformerConfig(
        vocab_size=1024, d_model=2048, n_layers=10, n_heads=32, n_kv_heads=8, head_dim=64, d_ff=512, max_seq_len=2048,
        param_dtype=jnp.bfloat16, norm_eps=1e-5, layer_pattern=(mamba, mamba, attention, mamba, mamba),
        embed_multiplier=12.0, residual_multiplier=0.22, attention_multiplier=1 / 64, logits_divisor=8.0,
        tie_embeddings=True)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the engine asks how wide a pool's rows are
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(
        max_slots=8, max_seq=2048, page_size=128, total_pages=40, prefill_buckets=(512,), decode_block=8))
    state, tails, k_pages, v_pages = eng.cache
    assert state.shape == (8, 8, 128, 4096) and state.dtype == jnp.float32 and tails.shape == (8, 8, 3 * 4352)
    assert k_pages.shape == v_pages.shape == (2, 8, 40 * 128, 128)  # a head of 64 in rows of 128
    B = eng.ec.max_slots
    ints, floats = on_chip(jnp.zeros(B, jnp.int32)), on_chip(jnp.zeros(B, jnp.float32))
    compiled = eng._decode_jit.lower(
        params, tuple(on_chip(pool) for pool in eng.cache), ints, ints, on_chip(eng.d_page_tables),
        on_chip(jax.random.PRNGKey(0)), 8, floats, floats, ints).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + 4 and "ssd_step" in text and "paged_attn" in text
    # the state pool is 537 MB, a page pool 21 MB, the input projections' stack 279 MB
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    # the prefill program of the one bucket: each of a period's four chunked calls is an instruction of its own (a
    # call fused into the consumer that stacks the layers' states is no kernel to a trace's reader), the flash call
    # beside them, and the padded K and V rows are written into pools that are not turned round to meet them
    i32 = lambda *dims: on_chip(jnp.zeros(dims, jnp.int32))
    f32 = lambda *dims: on_chip(jnp.zeros(dims, jnp.float32))
    compiled = eng._prefill(512, 1).lower(
        params, tuple(on_chip(pool) for pool in eng.cache), i32(1, 512), i32(1), i32(1, 4), on_chip(jax.random.PRNGKey(0)),
        f32(1), f32(1), i32(1), i32(1)).compile()
    lines = compiled.as_text().splitlines()
    assert sum(" custom-call(" in line and "%ssd_chunk" in line.split("=")[0] for line in lines) == 4
    assert sum("tpu_custom_call" in line for line in lines) == 4 + 1
    pool = "bf16[2,8,5120,128]"
    assert not [line for line in lines if " copy(" in line and line.split("=")[1].lstrip().startswith(pool)]
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("K,N", [(2048, 1536), (1536, 2048)])
def test_the_grouped_matmul_lowers_with_every_expert_of_eight_layers_for_a_v5e(K, N, one_chip, no_compile_cache,
                                                                               monkeypatch):
    """ops/grouped_matmul.py where a chip holds ALL 64 experts of 8 routed layers at widths 2048 and 1536: a decode
    step's 512 pairs in tiles of 16 rows (8 pairs an expert expected, a tile or two each), the whole stack of 3.2 GB
    an operand and no copy of it among the temporaries."""
    from ray_tpu.ops import grouped_matmul as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    M = gm.plan_rows(128 * 4, 64, 16)
    assert M == 1536

    def arr(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(x, w, layer, tile_expert, n_tiles):
        return gm.expert_gmm(x, w, layer, tile_expert, n_tiles, tm=16)

    compiled = jax.jit(call).lower(arr((M, K), jnp.bfloat16), arr((8, 64, K, N), jnp.bfloat16), arr((), jnp.int32),
                                   arr((M // 16,), jnp.int32), arr((1,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1 and "expert_gmm" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


def test_the_decode_program_of_a_model_with_conv_layers_and_every_expert_held_compiles_for_a_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """llm/engine.py ``_decode_impl`` of a leading dense conv layer and two periods of (attention with a head norm,
    conv, conv, conv) at the serve cell's widths, all 64 experts of each routed layer here and a small vocabulary:
    the period scan around one paged call and four layers' three grouped matmuls, no kernel for a conv layer. The
    page pools' rows are a lane tile wide from allocation; the tails' one pool [7, slots, 2 x 2048] has no float32
    pool beside it, a walk takes 8 pages a grid step (pages counted in bfloat16), and the experts' stacks (2.4 GB a
    kind's gate matrices) and the pools are operands of no copy inside the loops: the temporaries stay small."""
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models.transformer import LayerKind, TransformerConfig, init_params

    conv = LayerKind("conv", 0, mixer="conv", conv_size=3)
    attention = LayerKind("attention", 32, rope_theta=1e6)
    cfg = TransformerConfig(
        vocab_size=1024, d_model=2048, n_layers=9, n_heads=32, n_kv_heads=8, head_dim=64, d_ff=11776, max_seq_len=3712,
        param_dtype=jnp.bfloat16, norm_eps=1e-5, layer_pattern=(conv, attention, conv, conv), n_dense_layers=1,
        n_experts=64, expert_top_k=4, experts_held=64, expert_d_ff=1536, router_score="sigmoid", router_bias=True,
        qk_norm=True, tie_embeddings=True)

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg)))
    assert params["kind_layers"]["conv"]["w_gate"].shape == (6, 64, 2048, 1536)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the engine asks how wide a pool's rows are
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(
        max_slots=128, max_seq=3712, page_size=128, total_pages=40, prefill_buckets=(512,), decode_block=8))
    tails, k_pages, v_pages = eng.cache
    assert tails.shape == (7, 128, 2 * 2048) and tails.dtype == jnp.bfloat16
    assert k_pages.shape == v_pages.shape == (2, 8, 40 * 128, 128) and eng.rules[1].group == 8
    B = eng.ec.max_slots
    ints, floats = on_chip(jnp.zeros(B, jnp.int32)), on_chip(jnp.zeros(B, jnp.float32))
    compiled = eng._decode_jit.lower(
        params, tuple(on_chip(pool) for pool in eng.cache), ints, ints, on_chip(eng.d_page_tables),
        on_chip(jax.random.PRNGKey(0)), 8, floats, floats, ints).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + 4 * 3 and "expert_gmm" in text and "paged_attn" in text
    for scope in ("in_proj", "short_conv", "out_gate", "qk_norm", "experts/route"):
        assert scope in text, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 96 << 20  # 51.9 MB as read; an expert stack is 1.2 GB


def test_the_train_step_at_the_train_cells_shapes_computes_no_product_twice_and_stays_small_for_a_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """models/transformer.py ``make_train_step`` at benchmarks/configs/mistral-7b-v0.3-l2.json's widths and its train
    settings (3 rows of 4096 + 1 tokens, remat ``dots``, ce_chunk 0). The loss head makes its gradients where it makes
    its logits, so the compiler has no [3, 4096, 32768] logits to compute again for the head's weight gradient
    (``fusion.284.remat`` before: 4.7% of a step; PERF.md section 6, PR 52): no rematerialised instruction is a
    matmul (a TPU's ``convolution``) or a fusion around one (what is rematerialised here is one copy, ``copy.241``,
    a relayout of q). And the head's walk is unrolled over FEW chunks, 4 of [3, 1024] positions, so the step stays
    near the program it was: a warm start reads, deserialises and loads the whole compiled step before its first
    step returns, and the form that unrolled 8 chunks was refused for its ``setup_s`` (ROADMAP S12 (e)). The bounds
    are what was read here plus a tenth: 546 equations in the step's jaxpr (425 before the head; 706 with 8 chunks),
    196 fusion instructions (163; 242)."""
    import json
    import os
    import re

    from ray_tpu.models import transformer as T

    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "benchmarks", "configs", "mistral-7b-v0.3-l2.json")) as f:
        model = json.load(f)
    train = dict(model["train"])
    rows, seq = train.pop("batch_rows"), 4096
    cfg = T.TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"], d_ff=model["intermediate_size"],
        max_seq_len=seq, rope_theta=model["rope_theta"], **train)
    assert (rows, cfg.vocab_size, cfg.ce_chunk, cfg.remat_policy) == (3, 32768, 0, "dots")
    assert T._loss_head_chunk(rows, seq, cfg.vocab_size, 2, cfg.ce_chunk) == seq // 4

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # attention_impl "auto" asks: the flash kernel
    init_state, train_step, _ = T.make_train_step(cfg)
    state = jax.tree.map(on_chip, jax.eval_shape(init_state, jax.random.PRNGKey(0)))
    batch = {c: on_chip(jnp.zeros((rows, seq + 1), jnp.int32)) for c in ("tokens", "segment_ids", "positions", "mask")}
    traced = jax.jit(train_step, donate_argnums=(0,)).trace(state, batch)
    assert len(traced.jaxpr.eqns) <= 600
    text = traced.lower().compile().as_text()
    bodies, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            bodies[name] = []
        elif name:
            bodies[name].append(line)
    products = {name for name, body in bodies.items() if any(re.search(r" (convolution|dot)\(", line) for line in body)}
    for line in text.splitlines():
        if re.search(r"%[\w.\-]*\.remat[\w.\-]* = ", line):
            assert not re.search(r" (convolution|dot)\(", line), line
            assert not set(re.findall(r"calls=%([\w.\-]+)", line)) & products, line
    assert len(re.findall(r"= [^=\n]*? fusion\(", text)) <= 215
    assert "loss_head" in text and text.count("tpu_custom_call") >= 4  # the flash forward twice under "dots", its backward's two


def _bench_config(architecture, config):
    """(the architecture file's module, the configuration) of benchmarks/, loaded by path."""
    import importlib.util
    import json
    import os

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
    spec = importlib.util.spec_from_file_location("bench_" + architecture, os.path.join(bench, "architectures", architecture + ".py"))
    arch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arch)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        return arch, json.load(f)


def _gathered_rows(text, width):
    """The rows of every ``gather`` instruction of a compiled module (loop bodies and fusions among them) whose
    result is rows of ``width`` columns: {rows: how many instructions}."""
    import collections
    import math
    import re

    found = collections.Counter()
    for dims in re.findall(r"= \w+\[([\d,]+)\]\S* gather\(", text):
        dims = [int(d) for d in dims.split(",")]
        if len(dims) >= 2 and dims[-1] == width:
            found[math.prod(dims[:-1])] += 1
    return dict(found)


def test_a_routed_pass_moves_the_pairs_it_holds_in_the_train_step_and_a_laguna_prompt_for_a_v5e(
        one_chip, no_compile_cache, monkeypatch):
    """models/transformer.py ``_token_rows`` / ``_row_tokens`` at two cells' shapes, as the TPU's compiler leaves
    them. ``make_train_step`` at benchmarks/configs/mellum2-12b-a2.5b-ep4-l4.json (2 rows of 8,192 + 1 tokens,
    passes of 4,096 tokens x 8 choices, 16 of 64 experts held: a plan of 36,864 rows of 2304): no gather's result
    is the pass's 32,768 pairs (the parent's twelve: the pairs read back, forward and again under remat, and the
    two backwards); the sums by token gather LEVEL_CHUNK rows a step of a loop and the tokens' 4,096 once, the
    way back gathers LAYOUT_CHUNK rows a step into a buffer nobody zeroes (``AllocateBuffer``); the one gather of
    the plan's 36,864 rows left is the forward's, out of the pass's 4,096 tokens (it runs at the speed its rows
    are written, PERF.md section 6, PR 60). The step holds 15.304 GB as compiled here, its parent's 15.218: the
    kept rows of the down product [36,864, 2304] stand where the pairs read back [32,768, 2304] stood, 19 MB a
    pass and 75 a layer's four, and the sums' float32 [4096, 2304] beside them. llm/engine.py's prefill program
    of bucket 2048 at benchmarks/configs/laguna-s-2.1-ep8.json (one pass of 2,048 tokens x 10 choices, 32 of
    256 held: a plan of 28,672 rows of 3072): no gather of the pass's 20,480 pairs, a loop's of LEVEL_CHUNK."""
    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models import transformer as T
    from ray_tpu.ops.grouped_matmul import plan_rows

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the flash and grouped-matmul kernels, a pool's rows
    arch, model = _bench_config("mellum2", "mellum2-12b-a2.5b-ep4-l4")
    train = dict(model["train"])
    rows, seq = train.pop("batch_rows"), 8192
    cfg = T.TransformerConfig(**arch.transformer_kwargs(model), **train)
    assert (rows, cfg.d_model, cfg.expert_top_k, cfg.experts_held, cfg.n_experts) == (2, 2304, 8, 16, 64)
    plan = plan_rows(T.PAIRS_A_PASS, cfg.experts_held, T._expert_tile(4096, cfg))
    assert plan == 36_864 and T._layout_chunk(plan, cfg) == T.LAYOUT_CHUNK
    init_state, train_step, _ = T.make_train_step(cfg)
    state = jax.tree.map(on_chip, jax.eval_shape(init_state, jax.random.PRNGKey(0)))
    batch = {c: on_chip(jnp.zeros((rows, seq + 1), jnp.int32)) for c in ("tokens", "segment_ids", "positions", "mask")}
    compiled = jax.jit(train_step, donate_argnums=(0,)).lower(state, batch).compile()
    text = compiled.as_text()
    gathered = _gathered_rows(text, cfg.d_model)  # the embedding's rows are the batch's 16,384
    assert set(gathered) == {T.LEVEL_CHUNK, 4096, T.LAYOUT_CHUNK, plan, rows * seq}, gathered
    assert "experts/layout" in text and "AllocateBuffer" in text
    m = compiled.memory_analysis()
    held = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes - m.alias_size_in_bytes
    assert held <= 15.31e9, held  # 15,303,832,576 as read here; the parent's step 15,217,817,600

    arch, model = _bench_config("laguna", "laguna-s-2.1-ep8")
    cfg = T.TransformerConfig(**{**arch.transformer_kwargs(model), "param_dtype": jnp.bfloat16})
    bucket = 2048
    assert (cfg.d_model, cfg.expert_top_k, cfg.experts_held, cfg.n_experts) == (3072, 10, 32, 256)
    plan = plan_rows(bucket * cfg.expert_top_k, cfg.experts_held, T._expert_tile(bucket, cfg))
    assert plan == 28_672 and T._layout_chunk(plan, cfg) == T.LAYOUT_CHUNK
    params = jax.tree.map(on_chip, jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), cfg)))
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(
        max_slots=8, max_seq=bucket + 128, page_size=128, total_pages=40, prefill_buckets=(bucket,), decode_block=8))
    i32 = lambda *dims: on_chip(jnp.zeros(dims, jnp.int32))  # noqa: E731
    f32 = lambda *dims: on_chip(jnp.zeros(dims, jnp.float32))  # noqa: E731
    text = eng._prefill(bucket, 1).lower(
        params, tuple(on_chip(pool) for pool in eng.cache), i32(1, bucket), i32(1), i32(1, bucket // 128),
        on_chip(jax.random.PRNGKey(0)), f32(1), f32(1), i32(1), i32(1)).compile().as_text()
    gathered = _gathered_rows(text, cfg.d_model)  # the prompt's 2,048 tokens: the embedding's rows and the sums' own order
    assert set(gathered) == {T.LEVEL_CHUNK, bucket, plan}, gathered
    assert "experts/layout" in text


FLASH_SHAPES = {
    # B, S, H, KV, D, blocks, window, with the backward
    "the train cell's call: four sub-tiles a block": (3, 4096, 32, 8, 128, 1024, 0, True),
    "a sliding layer's prompt of 8,192 tokens": (1, 8192, 48, 8, 128, 512, 512, False),
    "a full layer's prompt at a head size under a lane tile": (2, 2048, 16, 4, 64, 512, 0, False),
}


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_the_flash_calls_lower_for_a_v5e(shape, one_chip, no_compile_cache, monkeypatch):
    """The walk of a block's live sub-tiles (PR 53) by the TPU's own compiler: slices of a block's refs at a loop's
    index along sublanes and along lanes, scalar tables read in the index maps and in the body, a value a row
    repeated along the lanes, dk/dv's transposed scores; with ids, as every caller passes them."""
    from ray_tpu.ops.attention import flash_attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, S, H, KV, D, blocks, window, backward = FLASH_SHAPES[shape]

    def arr(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def call(q, k, v, ids):
        return flash_attention(q, k, v, segment_ids=ids, window=window, block_q=blocks, block_k=blocks)

    if backward:
        call = jax.grad(lambda q, k, v, ids, f=call: jnp.sum(f(q, k, v, ids).astype(jnp.float32)), argnums=(0, 1, 2))
    args = (arr((B, S, H, D), jnp.bfloat16), arr((B, S, KV, D), jnp.bfloat16), arr((B, S, KV, D), jnp.bfloat16),
            arr((B, S), jnp.int32))
    text = jax.jit(call).lower(*args).compile().as_text()
    assert "flash_attn_fwd" in text and text.count("tpu_custom_call") == (3 if backward else 1)
    assert ("flash_attn_dq" in text and "flash_attn_dkv" in text) == backward
