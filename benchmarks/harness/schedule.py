"""The one traffic generator: a traffic file's parameters -> the work of a run.

Steady by construction. For a window of S seconds at rate r a serve cell
measures exactly N = round(r*S) requests, whose prompt and output lengths are
the N stratified quantiles of the two distributions: the same multiset for
every seed. The seed only permutes them (which pairs prompts with outputs),
draws the token ids and draws the arrival times. So requests, prompt tokens
and output tokens offered are identical in every run; order and timing vary.
A train cell packs a fixed list of documents once, in a seed-independent
order, and the seed permutes the packed rows and draws the token ids.

numpy and the standard library only: the load generator and the harness's
driver import this, and neither may touch jax.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (mid-points of n equal-probability strata)
    of a clipped length distribution, ascending. No randomness."""
    if n <= 0:
        return np.zeros(0, np.int64)
    kind = dist["dist"]
    u = (np.arange(n) + 0.5) / n
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        vals = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "fixed":
        vals = np.full(n, float(dist["value"]))
        return np.rint(vals).astype(np.int64)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def arrival_times(rng: np.random.Generator, n: int, span_s: float, shape: float) -> np.ndarray:
    """n due times inside (0, span_s): gamma gaps of the given shape
    (CV = shape**-0.5), rescaled so that the n+1 gaps fill the span exactly."""
    if n <= 0:
        return np.zeros(0)
    gaps = rng.gamma(shape, 1.0, n + 1)
    return span_s * np.cumsum(gaps)[:n] / gaps.sum()


def _phase_requests(rng, traffic: dict, n: int, t0: float, span_s: float, phase: str) -> list[dict]:
    prompts = rng.permutation(quantile_lengths(traffic["prompt_len"], n))
    outputs = rng.permutation(quantile_lengths(traffic["output_len"], n))
    due = t0 + arrival_times(rng, n, span_s, float(traffic.get("arrival_shape", 4)))
    reqs = [
        {"due": float(due[i]), "prompt_len": int(prompts[i]), "out_len": int(outputs[i]),
         "phase": phase}
        for i in range(n)
    ]
    turns = traffic.get("turns")
    if turns and int(turns["count"]) > 1:
        # A session: each later turn sends the conversation so far (what was
        # sent and what came back) plus add_len new tokens, think_s after the
        # turn before it completed. Every turn's lengths are stratified too.
        extra = int(turns["count"]) - 1
        adds = rng.permutation(quantile_lengths(turns["add_len"], n * extra))
        outs = rng.permutation(quantile_lengths(traffic["output_len"], n * extra))
        for i, r in enumerate(reqs):
            r["later_turns"] = [
                {"add_len": int(adds[i * extra + j]), "out_len": int(outs[i * extra + j])}
                for j in range(extra)
            ]
            r["think_s"] = float(turns.get("think_s", 1.0))
    return reqs


def _tenant_of(rng, prefix: dict, n: int) -> np.ndarray:
    tenants = int(prefix.get("tenants", 1))
    s = float(prefix.get("zipf_s", 0.0))
    w = 1.0 / np.arange(1, tenants + 1) ** s
    # Stratified as the lengths are: every seed offers each tenant the same
    # number of sessions; the seed decides which.
    counts = np.floor(w / w.sum() * n).astype(int)
    counts[: n - counts.sum()] += 1
    return rng.permutation(np.repeat(np.arange(tenants), counts))


def serve_plan(traffic: dict, seed: int, seconds: float, max_slots: int) -> dict:
    """Everything the load generator needs, as plain data.

    open loop:   requests with due times relative to the start of the ramp;
                 phases ramp (unmeasured), window (measured), cooldown
                 (unmeasured, keeps the load up while the window drains).
    closed loop: an ordered list the clients draw from, long enough that it
                 cannot run out; `concurrency` clients.
    """
    rng = np.random.default_rng([int(seed), 0x5EED])
    ramp_s = float(traffic.get("ramp_s", 0.0))
    plan = {"loop": traffic["loop"], "ramp_s": ramp_s, "seconds": float(seconds),
            "token_seed": int(seed), "prefix": traffic.get("prefix") or {}}
    if traffic["loop"] == "open":
        rate = float(traffic["rate_rps"])
        cool_s = float(traffic.get("cooldown_s", 0.0))
        reqs = _phase_requests(rng, traffic, round(rate * ramp_s), 0.0, ramp_s, "ramp")
        reqs += _phase_requests(rng, traffic, round(rate * seconds), ramp_s, float(seconds), "window")
        reqs += _phase_requests(rng, traffic, round(rate * cool_s), ramp_s + seconds, cool_s, "cooldown")
        plan["cooldown_s"] = cool_s
    elif traffic["loop"] == "closed":
        conc = int(traffic.get("concurrency") or round(float(traffic["concurrency_x_slots"]) * max_slots))
        plan["concurrency"] = conc
        # One fixed multiset, repeated in fresh seeded orders: any stretch of
        # it that a run gets through has close to the same mix.
        per = int(traffic.get("multiset", 256))
        cycles = int(traffic.get("cycles", 8))
        reqs = []
        for _ in range(cycles):
            reqs += _phase_requests(rng, traffic, per, 0.0, 0.0, "stream")
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r} (open|closed)")
    prefix = plan["prefix"]
    tenants = _tenant_of(rng, prefix, len(reqs)) if prefix.get("tenants", 1) > 1 else None
    for i, r in enumerate(reqs):
        r["idx"] = i
        r["tenant"] = int(tenants[i]) if tenants is not None else 0
    plan["requests"] = reqs
    return plan


def plan_totals(plan: dict, phase: str) -> dict:
    rs = [r for r in plan["requests"] if r["phase"] == phase]
    return {"requests": len(rs), "prompt_tokens": sum(r["prompt_len"] for r in rs),
            "output_tokens": sum(r["out_len"] for r in rs)}


def prompt_tokens(token_seed: int, idx: int, n: int, vocab: int) -> list[int]:
    """Request idx's own (unshared) tokens: the same for a seed, whoever asks."""
    rng = np.random.default_rng([int(token_seed), 0x70C, int(idx)])
    return rng.integers(0, vocab, n).tolist()


def tenant_prefix(token_seed: int, tenant: int, n: int, vocab: int) -> list[int]:
    rng = np.random.default_rng([int(token_seed), 0x7E4, int(tenant)])
    return rng.integers(0, vocab, n).tolist()


# ---------------------------------------------------------------------------
# Training: documents packed into fixed-length rows
# ---------------------------------------------------------------------------

def pack_documents(doc_lens: np.ndarray, seq_len: int) -> list[list[int]]:
    """Greedy first-fit: each document, in order, goes into the first row
    that still has room for it, else opens a new row; what a row has left at
    the end is padding. A document longer than a row is cut to it. Returns
    the document lengths of each row."""
    rows, room = [], []
    for n in (int(min(x, seq_len)) for x in doc_lens):
        at = next((i for i, r in enumerate(room) if r >= n), None)
        if at is None:
            rows.append([])
            room.append(seq_len)
            at = len(rows) - 1
        rows[at].append(n)
        room[at] -= n
    return rows


def train_rows(traffic: dict) -> list[list[int]]:
    """The packed rows of a train cell, the same for every seed. Packing
    yields rows of seq_len+1 tokens (inputs and shifted targets). Documents
    are interleaved long/short by a fixed stride so that rows are alike."""
    row_len = int(traffic["seq_len"]) + 1
    n_docs = int(traffic["documents"])
    lens = quantile_lengths(traffic["doc_len"], n_docs)
    stride = next(s for s in range(max(2, int(n_docs * 0.381966)), n_docs + 2) if math.gcd(s, n_docs) == 1)
    order = (np.arange(n_docs) * stride) % n_docs
    if traffic.get("packing", "greedy") == "greedy":
        return pack_documents(lens[order], row_len)
    if traffic["packing"] == "none":
        return [[int(min(x, row_len))] for x in lens[order]]
    raise ValueError(f"unknown packing {traffic['packing']!r} (greedy|none)")


def train_arrays(traffic: dict, seed: int, vocab: int) -> dict:
    """tokens / segment_ids / positions / mask, each [rows, seq_len+1] int32.
    Segment 0 is padding; a document's positions restart at 0."""
    rows = train_rows(traffic)
    row_len = int(traffic["seq_len"]) + 1
    rng = np.random.default_rng([int(seed), 0x7EA1])
    rows = [rows[i] for i in rng.permutation(len(rows))]
    n = len(rows)
    tokens = rng.integers(0, vocab, (n, row_len), dtype=np.int32)
    seg = np.zeros((n, row_len), np.int32)
    pos = np.zeros((n, row_len), np.int32)
    for r, docs in enumerate(rows):
        at = 0
        for s, d in enumerate(docs, start=1):
            seg[r, at:at + d] = s
            pos[r, at:at + d] = np.arange(d)
            at += d
    mask = (seg > 0).astype(np.int32)
    tokens *= mask
    return {"tokens": tokens, "segment_ids": seg, "positions": pos, "mask": mask,
            "doc_lens": rows}


def trained_tokens_per_row(doc_lens: list[list[int]]) -> np.ndarray:
    """Target positions that carry loss: every token of a document but its
    first (the loss never predicts across a boundary or into padding)."""
    return np.array([sum(d - 1 for d in docs if d > 1) for docs in doc_lens], np.int64)
