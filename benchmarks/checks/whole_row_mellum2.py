"""The comparison a train cell's check cannot make (harness/train_cell.py reads 1,024 tokens' loss of one row,
which never leaves one window of 1024, and no gradient): ONE WHOLE packed row of `mellum2-12b-ep4.pretrain-8k`
at the published widths, the program's loss AND gradients (the Pallas kernels, bfloat16 activations) against the
float32 plain reference under default_matmul_precision("highest") with its attention in blocks of queries, leaf
by leaf in relative L2, held to LIMITS; and three controls, each of which at least one limit must refuse: the
band one sub-tile too wide, every token's weakest pair dropped, the grouped matmul's operands in 8 bits.

    chiprun --timeout 1800 -- python3 benchmarks/checks/whole_row_mellum2.py --seeds 11,2158000311 \\
        --controls window,pairs,fp8          # controls run on the last seed; about 4 minutes a seed on a v5e
    python3 benchmarks/checks/whole_row_mellum2.py --toy      # the CPU, toy widths: runs, judges nothing
    python3 benchmarks/checks/whole_row_mellum2.py --describe # compiles both programs for a described v5e

Prints one JSON line a run and a last line {"sound": ..., "controls_refused": ...}; exit code 1 where a sound run
passes a limit or a control stays under every one. Run it after any change to ops/grouped_matmul.py's backward,
to the windowed flash calls or to _held_experts_pass (PERF.md section 7, From PR 59 (b))."""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from harness import cellspec, schedule  # noqa: E402

from ray_tpu.models import reference_window_softmax_moe as ref  # noqa: E402
from ray_tpu.models import transformer  # noqa: E402
from ray_tpu.models.reference_window_moe import _rope as rope_of  # noqa: E402
from ray_tpu.models.transformer import TransformerConfig, cross_entropy_loss, init_params  # noqa: E402
from ray_tpu.ops import grouped_matmul  # noqa: E402

# Each limit lies between the largest sound reading over 12 seeds and the smallest reading of the controls, with
# room on both sides (a v5e, PR 59's builder: PERF.md section 6). Sound, least-most: loss gap 0.00006-0.00028;
# sliding wq 0.051-0.068, wo 0.026-0.036; full wq 0.044-0.059, wo 0.020-0.031; routers 0.084-0.104 (sliding) and
# 0.089-0.160 (full); a held expert's three matrices 0.057-0.092; head 0.019-0.027; embedding 0.034-0.049: a
# bfloat16 residual stream moves a few percent of the tokens' eighth choice to another expert than float32
# picks, so the routers and the experts read highest. Controls on seed 2158000311 (loss gap; sliding wo; full
# wo; head; expert w_gate): window + 512: 0.0028, 0.221, 0.165, 0.190, 0.351; weakest pair dropped: 0.0004,
# 0.079, 0.055, 0.062, 0.205 (the loss does not see it, every leaf does); 8-bit operands: 0.0026, 0.192, 0.147,
# 0.154, 0.489.
LIMITS = {"loss_gap": 0.001, "sliding.wq": 0.10, "sliding.wo": 0.05, "full.wq": 0.09, "full.wo": 0.04,
          "sliding.router": 0.16, "full.router": 0.24, "expert.w_gate": 0.14, "expert.w_up": 0.14,
          "expert.w_down": 0.14, "lm_head": 0.04, "embed": 0.07}

ap = argparse.ArgumentParser()
ap.add_argument("--seeds", default="0", help="comma-separated; each draws the weights and picks the row")
ap.add_argument("--controls", default="none", help="of window,pairs,fp8: run on the last seed")
ap.add_argument("--toy", action="store_true", help="the rehearsal's widths, for the CPU: nothing is judged")
ap.add_argument("--row", type=int, default=-1, help="this row of the seed's arrays and not the one it picks")
ap.add_argument("--describe", action="store_true", help="compile both programs for a described v5e and stop")
args = ap.parse_args()

spec = cellspec.load_cell("mellum2-12b-ep4.pretrain-8k")
if args.toy:
    spec = cellspec.shrink_for_rehearsal(spec)
config, traffic = spec["config"], spec["traffic"]
S = int(traffic["seq_len"])
kw = cellspec.transformer_kwargs(config)
kw["max_seq_len"] = S
trn = dict(config["train"])
trn.pop("batch_rows")
if args.toy:
    kw.update(dtype=jnp.bfloat16)
cfg = TransformerConfig(**kw, **trn)
held = (cfg.first_expert, cfg.experts_held)
BLOCK = 512 if not args.toy else 32
COLS = ("tokens", "segment_ids", "positions", "mask")


def a_row(seed):
    """A row of several documents, one of them past two and a half windows, of that seed's arrays."""
    arrays = schedule.train_arrays(traffic, seed, config["vocab_size"])
    rows = [i for i, d in enumerate(arrays["doc_lens"]) if len(d) >= 3 and max(d) > 2.5 * config["sliding_window"]]
    r = args.row if args.row >= 0 else rows[seed % len(rows)]
    return r, arrays["doc_lens"][r], {c: jnp.asarray(arrays[c][r:r + 1]) for c in COLS}


def blocked_attention(h, lp, rope, window, positions, allowed_unused, seg):
    """ref.attention with the queries in blocks: the same mask, scores [H, BLOCK, S] at a time."""
    F32 = jnp.float32
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, lp[n].astype(F32)) for n in ("wq", "wk", "wv"))
    q, k = rope_of(q, positions, rope), rope_of(k, positions, rope)
    H, KV, S_ = q.shape[2], k.shape[2], q.shape[1]
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    j = jnp.arange(S_)[None, :]

    @jax.checkpoint
    def block(at):
        i = at + jnp.arange(BLOCK)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, at, BLOCK, axis=1)
        sb = jax.lax.dynamic_slice_in_dim(seg, at, BLOCK, axis=1)
        ok = (j <= i) & (sb[:, :, None] == seg[:, None, :])
        if window:
            ok = ok & (j > i - window)
        s = jnp.einsum("bqhk,bthk->bhqt", qb, k) / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(ok[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqt,bthk->bqhk", p, v)

    a = jax.lax.map(block, jnp.arange(0, S_, BLOCK))  # [n, B, BLOCK, H, d]
    a = jnp.moveaxis(a, 0, 1).reshape(q.shape)
    return jnp.einsum("bshk,hkd->bsd", a, lp["wo"].astype(F32))


def reference_loss(tree, batch):
    """ref.packed_loss with each layer under jax.checkpoint and the attention in blocks of queries; `tree` is
    the program's with its stacks taken apart, ``layers`` a list of one dict a layer: the gradient of a layer's
    slice is then no stack of zeros around it."""
    tok, seg, pos = batch["tokens"], batch["segment_ids"], batch["positions"]
    tokens, segs, positions = tok[:, :-1], seg[:, :-1], pos[:, :-1]
    eps = float(config["rms_norm_eps"])
    x = tree["embed"].astype(jnp.float32)[tokens]
    balance = jnp.zeros((), jnp.float32)

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def layer(x, lp, kind):
        window = config["sliding_window"] if kind == ref.SLIDING else 0
        h = ref._norm(x, lp["attn_norm"], eps)
        x = x + blocked_attention(h, lp, config["rope_parameters"][kind], window, positions, None, segs)
        out, term = ref.routed_ffn(ref._norm(x, lp["ffn_norm"], eps), lp, config, held)
        return x + out, term

    for kind, lp in zip(config["layer_types"], tree["layers"]):
        x, term = layer(x, lp, kind)
        balance = balance + term
    lg = ref._norm(x, tree["final_norm"], eps) @ tree["lm_head"].astype(jnp.float32)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1), tok[:, 1:, None], axis=-1)[..., 0]
    w = ((seg[:, 1:] == seg[:, :-1]) & (batch["mask"][:, 1:] > 0)).astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.sum(w) + float(config["router_aux_loss_coef"]) * balance


def apart(params):
    """The program's tree with ``kind_layers`` taken apart into a list of one dict a layer, in order."""
    seen, layers = {}, []
    for kind in config["layer_types"]:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        layers.append({k: v[i] for k, v in params["kind_layers"][kind].items()})
    return {"embed": params["embed"], "final_norm": params["final_norm"], "lm_head": params["lm_head"], "layers": layers}


# layers 0-2 are sliding (the stack's 0-2), layer 3 the full one
PICKS = {"sliding.wq": (1, "wq"), "sliding.wo": (1, "wo"), "full.wq": (3, "wq"), "full.wo": (3, "wo"),
         "sliding.router": (1, "router"), "full.router": (3, "router"),
         "expert.w_gate": (1, "w_gate", 3), "expert.w_up": (1, "w_up", 3), "expert.w_down": (1, "w_down", 3)}


def picked(tree):
    """The compared leaves of a tree taken apart: small copies, so that the whole gradient can go."""
    out = {name: (tree["layers"][at][leaf] if not e else tree["layers"][at][leaf][e[0]]) + 0
           for name, (at, leaf, *e) in PICKS.items()}
    out.update({"lm_head": tree["lm_head"] + 0, "embed": tree["embed"] + 0})
    return out


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.linalg.norm(b.ravel()))


if args.describe:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    one = SingleDeviceSharding(topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"
    sh = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)  # noqa: E731
    p_s = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    b_s = sh({c: jax.ShapeDtypeStruct((1, S + 1), jnp.int32) for c in COLS})
    for name, fn, tree in (("program", lambda p, b: cross_entropy_loss(p, b, cfg), sh(p_s)),
                           ("reference", reference_loss, sh(jax.eval_shape(apart, p_s)))):
        t0 = time.time()
        with jax.default_matmul_precision("highest" if name == "reference" else "default"):
            m = jax.jit(jax.value_and_grad(fn)).lower(tree, b_s).compile().memory_analysis()
        print(name, "compile s", round(time.time() - t0, 1), "GB args/out/temp",
              [round(x / 1e9, 2) for x in (m.argument_size_in_bytes, m.output_size_in_bytes, m.temp_size_in_bytes)], flush=True)
    raise SystemExit(0)

reference = jax.jit(jax.value_and_grad(reference_loss))
program = lambda c: jax.jit(jax.value_and_grad(lambda p, b: cross_entropy_loss(p, b, c)))  # noqa: E731
make = jax.jit(lambda k: init_params(k, cfg))
plain_matmul, plain_group = grouped_matmul.expert_matmul, grouped_matmul.group_rows
seeds = [int(x) for x in args.seeds.split(",")]
own = program(cfg)
sound, refused = [], {}
for n, seed in enumerate(seeds):
    params = make(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
    r, docs, batch = a_row(seed)
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = reference(apart(params), batch)
        ref_loss, want = float(ref_loss), picked(ref_grads)
        jax.block_until_ready(want)
    del ref_grads
    print(json.dumps({"seed": seed, "row": int(r), "docs": [int(d) for d in docs], "reference_loss": ref_loss,
                      "reference_s": round(time.time() - t0, 1), "device": jax.devices()[0].device_kind}), flush=True)
    for control in ["none"] + ([c for c in args.controls.split(",") if c != "none"] if n == len(seeds) - 1 else []):
        fn = own
        if control != "none":
            jax.clear_caches()  # jax.checkpoint keeps a function's trace: a patched global would not be read again
            fn = program(cfg)
        if control == "window":  # the band one sub-tile of 512 too wide
            wider = 512 if not args.toy else 16
            pattern = tuple(transformer.LayerKind(**{**k.__dict__, "window": k.window + wider}) if k.window else k
                            for k in cfg.layer_pattern)
            fn = program(TransformerConfig(**{**cfg.__dict__, "layer_pattern": pattern}))
        elif control == "pairs":  # every token's weakest chosen pair dropped (an eighth of the pairs)
            def group_rows_dropping(experts, first, n_held, tm):
                return plain_group(experts.at[:, -1].set(first + n_held + 1_000_000), first, n_held, tm)
            grouped_matmul.group_rows = group_rows_dropping
        elif control == "fp8":  # the grouped matmul's rows and weights rounded to 8 bits (float8_e4m3fn) before each product
            def eight_bit():
                mm = plain_matmul()
                # the product's operands in 8 bits, the gradient straight through
                r8 = lambda a: a + jax.lax.stop_gradient(jax.lax.reduce_precision(a, 4, 3) - a)  # noqa: E731  (a convert there and back the TPU compiler takes out: excess precision is allowed)
                return lambda x, w, **kw: mm(r8(x), r8(w), **kw)
            grouped_matmul.expert_matmul = eight_bit
        t0 = time.time()
        loss, grads = fn(params, batch)
        loss, got = float(loss), picked(apart(grads))
        del grads
        read = {"loss_gap": abs(loss - ref_loss), **{name: rel(got[name], want[name]) for name in want}}
        over = sorted(name for name in read if not read[name] <= LIMITS[name])  # a NaN is over
        if control == "none":
            sound.append(not over)
        else:
            refused[control] = bool(over)
        print(json.dumps({"control": control, "seed": seed, "loss": loss, "program_s": round(time.time() - t0, 1),
                          "read": read, "over_its_limit": over}), flush=True)
        grouped_matmul.expert_matmul, grouped_matmul.group_rows = plain_matmul, plain_group
    del params, want
stats = jax.devices()[0].memory_stats() or {}
print(json.dumps({"sound": sound, "controls_refused": refused, "judged": not args.toy,
                  "peak_bytes_in_use": stats.get("peak_bytes_in_use")}))
if not args.toy and not (all(sound) and all(refused.values())):
    raise SystemExit(1)
