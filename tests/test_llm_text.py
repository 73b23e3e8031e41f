"""Text-in/text-out LLM serving: tokenizer, per-request sampling, and the
OpenAI-compatible ingress (reference: llm/_internal/serve/core/ingress/
ingress.py:145 /v1 routes; vLLM per-request SamplingParams)."""
import json
import socket
import time

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams, Tokenizer
from ray_tpu.models import TransformerConfig

CFG = TransformerConfig(
    vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
)

CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "the quick brown fox is quick and the dog is lazy",
    "distributed systems schedule tasks over the cluster",
    "the scheduler places the tasks on the nodes of the cluster",
] * 4


# ---------------------------------------------------------------------------
# tokenizer
# ---------------------------------------------------------------------------

def test_tokenizer_roundtrip_any_unicode():
    tok = Tokenizer()  # merge-less: pure byte fallback
    for s in ("hello world", "héllo wörld", "日本語のテスト", "mixed 英語 & emoji 🎉", ""):
        assert tok.decode(tok.encode(s)) == s


def test_tokenizer_train_compresses_and_roundtrips(tmp_path):
    tok = Tokenizer.train(CORPUS, vocab_size=3 + 256 + 64)
    assert len(tok.merges) > 0
    s = "the quick brown fox jumps over the lazy dog"
    ids = tok.encode(s)
    assert tok.decode(ids) == s
    # Learned merges beat byte fallback on in-domain text.
    assert len(ids) < len(Tokenizer().encode(s))
    # Round-trips out-of-domain text too (byte fallback).
    assert tok.decode(tok.encode("zebra xylophone 🦓")) == "zebra xylophone 🦓"
    # Persistence.
    p = str(tmp_path / "tok.json")
    tok.save(p)
    tok2 = Tokenizer.load(p)
    assert tok2.encode(s) == ids
    assert tok2.vocab_size == tok.vocab_size


def test_tokenizer_specials():
    tok = Tokenizer()
    ids = tok.encode("hi", add_bos=True, add_eos=True)
    assert ids[0] == tok.bos_id and ids[-1] == tok.eos_id
    assert tok.decode(ids) == "hi"  # specials render as nothing


def test_tokenizer_deep_merge_chain_decodes():
    """A degenerate corpus can learn a merge chain nested deeper than
    Python's recursion limit; decode must expand iteratively."""
    import sys

    base = 3 + ord("a")  # byte token for 'a'
    depth = sys.getrecursionlimit() + 500
    merges = [(base, base)] + [(3 + 256 + i, base) for i in range(depth - 1)]
    tok = Tokenizer(merges)
    deepest = 3 + 256 + len(merges) - 1
    assert tok.decode([deepest]) == "a" * (depth + 1)


# ---------------------------------------------------------------------------
# per-request sampling
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    return LLMEngine(CFG, engine_config=EngineConfig(
        max_slots=4, max_seq=128, page_size=16, prefill_buckets=(16, 32)))


def _drain(engine):
    results = {}
    while engine.has_work():
        for rid, ev in engine.step().items():
            if ev.get("finished"):
                results[rid] = ev["tokens"]
    return results


def test_mixed_batch_greedy_rows_stay_deterministic(engine):
    """One batch holding a greedy row and a hot sampled row: the greedy
    row's output must equal its solo run (per-row params, no bleed)."""
    prompt = np.array([5, 17, 42, 7, 23], np.int32)
    solo = engine.generate(prompt, max_tokens=10)["tokens"]
    engine.add_request("greedy", prompt, sampling=SamplingParams(temperature=0.0, max_tokens=10))
    engine.add_request("hot", prompt, sampling=SamplingParams(temperature=1.5, max_tokens=10))
    results = _drain(engine)
    assert results["greedy"] == solo
    assert len(results["hot"]) == 10


def test_topk1_equals_greedy(engine):
    """top_k=1 at any temperature collapses to argmax."""
    prompt = np.array([3, 1, 4, 1, 5], np.int32)
    solo = engine.generate(prompt, max_tokens=8)["tokens"]
    engine.add_request("k1", prompt, sampling=SamplingParams(temperature=2.0, top_k=1, max_tokens=8))
    assert _drain(engine)["k1"] == solo


def test_temperature_actually_randomizes(engine):
    """Two hot rows with the same prompt in one batch should (overwhelmingly)
    diverge — the per-request temperature is really applied."""
    prompt = np.array([9, 9, 9, 9], np.int32)
    sp = SamplingParams(temperature=3.0, max_tokens=16)
    engine.add_request("h1", prompt, sampling=sp)
    engine.add_request("h2", prompt, sampling=sp)
    results = _drain(engine)
    assert results["h1"] != results["h2"]


def test_stop_token_ids(engine):
    """A per-request stop token retires the request the moment it appears."""
    prompt = np.array([5, 17, 42, 7, 23], np.int32)
    solo = engine.generate(prompt, max_tokens=10)["tokens"]
    stop_tok = solo[3]
    engine.add_request("s", prompt, sampling=SamplingParams(
        max_tokens=10, stop_token_ids=(int(stop_tok),)))
    got = _drain(engine)["s"]
    assert got == solo[:4]  # stops AT the stop token (inclusive emission)


def test_finish_reason_length_at_context_cap(engine):
    """A request force-retired at the max_seq context ceiling reports
    finish_reason 'length' even though fewer than max_tokens were generated
    (previously mislabeled 'stop' by the under-max_tokens heuristic)."""
    prompt = [3 + (i % 200) for i in range(120)]  # 120 of 128 context
    engine.add_request("ctxcap", prompt, sampling=SamplingParams(max_tokens=64))
    reasons = {}
    while engine.has_work():
        for rid, ev in engine.step().items():
            if ev.get("finished"):
                reasons[rid] = (ev.get("finish_reason"), len(ev["tokens"]))
    reason, n = reasons["ctxcap"]
    assert n < 64, "context cap should have cut generation short"
    assert reason == "length", reasons


def test_top_p_restricts_support(engine):
    """top_p≈0 keeps only the most probable token -> equals greedy."""
    prompt = np.array([2, 7, 1, 8], np.int32)
    solo = engine.generate(prompt, max_tokens=8)["tokens"]
    engine.add_request("p", prompt, sampling=SamplingParams(
        temperature=1.0, top_p=1e-6, max_tokens=8))
    assert _drain(engine)["p"] == solo


# ---------------------------------------------------------------------------
# OpenAI-compatible ingress end-to-end over the HTTP proxy
# ---------------------------------------------------------------------------

def _http(port, method, path, payload=None, timeout=120):
    body = json.dumps(payload).encode() if payload is not None else b""
    s = socket.create_connection(("127.0.0.1", port), timeout=timeout)
    req = (
        f"{method} {path} HTTP/1.1\r\nhost: x\r\ncontent-type: application/json\r\n"
        f"content-length: {len(body)}\r\nconnection: close\r\n\r\n"
    ).encode() + body
    s.sendall(req)
    raw = b""
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        raw += chunk
    s.close()
    head, _, rest = raw.partition(b"\r\n\r\n")
    status = head.split(b"\r\n", 1)[0].decode()
    headers = {}
    for line in head.split(b"\r\n")[1:]:
        k, _, v = line.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
    if headers.get("transfer-encoding") == "chunked":
        body_out = b""
        while rest:
            size_line, _, rest = rest.partition(b"\r\n")
            size = int(size_line.strip() or b"0", 16)
            if size == 0:
                break
            body_out += rest[:size]
            rest = rest[size + 2:]
        return status, headers, body_out
    return status, headers, rest


TINY_MODEL = dict(
    vocab_size=512, d_model=32, n_layers=1, n_heads=2, n_kv_heads=2,
    d_ff=64, max_seq_len=64, attention_impl="reference",
)
TINY_ENGINE = {"max_slots": 2, "max_seq": 64, "page_size": 16, "prefill_buckets": (16,)}

CHATML = (
    "{% for message in messages %}"
    "<|im_start|>{{ message.role }}\n{{ message.content }}<|im_end|>\n"
    "{% endfor %}"
    "{% if add_generation_prompt %}<|im_start|>assistant\n{% endif %}"
)

CONVERSATION = [
    {"role": "system", "content": "You are a helpful assistant."},
    {"role": "user", "content": "What is a TPU?"},
    {"role": "assistant", "content": "A matrix-multiply accelerator."},
    {"role": "user", "content": "Thanks!"},
]


def test_chat_template_jinja_golden():
    """A jinja chat template renders a multi-turn conversation into the
    exact prompt format the checkpoint expects (golden: ChatML, the format
    Qwen-family checkpoints are tuned on)."""
    from ray_tpu.llm.openai import OpenAIServer

    srv = OpenAIServer(TINY_MODEL, TINY_ENGINE, chat_template=CHATML)
    got, templated = srv._chat_prompt(CONVERSATION)
    assert templated  # rendered prompts must not get a second BOS
    assert got == (
        "<|im_start|>system\nYou are a helpful assistant.<|im_end|>\n"
        "<|im_start|>user\nWhat is a TPU?<|im_end|>\n"
        "<|im_start|>assistant\nA matrix-multiply accelerator.<|im_end|>\n"
        "<|im_start|>user\nThanks!<|im_end|>\n"
        "<|im_start|>assistant\n"
    )
    srv.__raytpu_exit__()


def test_chat_template_tokenizer_precedence(monkeypatch):
    """No explicit template + a tokenizer that ships one (HF checkpoints
    do) -> the checkpoint's own template is used; an explicit template
    still wins; without either, the legacy role:content fallback."""
    import ray_tpu.llm.openai as oai

    class TokWithTemplate:
        eos_id, bos_id, vocab_size = 2, 1, 512
        chat_template = "non-none"

        def encode(self, text, add_bos=False, add_eos=False):
            return [1, 3, 4]

        def decode(self, ids):
            return "x"

        def apply_chat_template(self, messages, add_generation_prompt=True):
            return "|".join(m["role"] for m in messages) + (
                "|gen" if add_generation_prompt else "")

    monkeypatch.setattr(oai, "load_tokenizer", lambda spec: TokWithTemplate())
    srv = oai.OpenAIServer(TINY_MODEL, TINY_ENGINE)
    assert srv._chat_prompt(CONVERSATION) == ("system|user|assistant|user|gen", True)
    srv.__raytpu_exit__()
    # Explicit jinja template beats the tokenizer's.
    srv2 = oai.OpenAIServer(TINY_MODEL, TINY_ENGINE, chat_template=CHATML)
    assert srv2._chat_prompt([{"role": "user", "content": "q"}])[0].startswith(
        "<|im_start|>user")
    srv2.__raytpu_exit__()


def test_chat_template_legacy_fallback():
    from ray_tpu.llm.openai import OpenAIServer

    srv = OpenAIServer(TINY_MODEL, TINY_ENGINE)  # byte tokenizer: no template
    got, templated = srv._chat_prompt([{"role": "user", "content": "hi"}])
    assert got == "user: hi\nassistant:" and not templated
    srv.__raytpu_exit__()


def test_openai_ingress_end_to_end():
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.llm import build_openai_app

    rt.init(num_cpus=8)
    serve.start()
    try:
        app = build_openai_app(
            model_config=dict(
                vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                d_ff=128, max_seq_len=128, attention_impl="reference",
            ),
            engine_config={"max_slots": 4, "max_seq": 128, "page_size": 16, "prefill_buckets": (16, 32)},
            model_name="tiny-test-model",
        )
        serve.run(app, name="oai", route_prefix="/")
        port = serve.http_port()

        # /v1/models
        status, _, body = _http(port, "GET", "/v1/models")
        assert "200" in status
        models = json.loads(body)
        assert models["data"][0]["id"] == "tiny-test-model"

        # /v1/completions non-streaming (greedy => deterministic).
        req = {"model": "tiny-test-model", "prompt": "hello world", "max_tokens": 8}
        status, _, body = _http(port, "POST", "/v1/completions", req)
        assert "200" in status, body
        out = json.loads(body)
        assert out["object"] == "text_completion"
        assert out["usage"]["completion_tokens"] == 8
        text1 = out["choices"][0]["text"]
        status, _, body = _http(port, "POST", "/v1/completions", req)
        assert json.loads(body)["choices"][0]["text"] == text1
        assert json.loads(body)["choices"][0]["finish_reason"] == "length"

        # Per-request temperature: a hot request through the SAME engine.
        hot = dict(req, temperature=3.0, top_p=0.95)
        status, _, body = _http(port, "POST", "/v1/completions", hot)
        assert "200" in status

        # /v1/chat/completions streaming: OpenAI chunk objects over SSE.
        chat = {
            "model": "tiny-test-model", "stream": True, "max_tokens": 8,
            "messages": [{"role": "user", "content": "hi there"}],
        }
        status, headers, body = _http(port, "POST", "/v1/chat/completions", chat)
        assert "200" in status
        assert headers.get("content-type") == "text/event-stream"
        frames = [line[6:] for line in body.decode().split("\n") if line.startswith("data: ")]
        assert frames[-1] == "[DONE]"
        chunks = [json.loads(f) for f in frames[:-1]]
        assert chunks[0]["object"] == "chat.completion.chunk"
        assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
        assert chunks[-1]["choices"][0]["finish_reason"] in ("stop", "length")

        # Error paths: missing prompt -> 400 with an OpenAI error body.
        status, _, body = _http(port, "POST", "/v1/completions", {"model": "m"})
        assert "400" in status
        assert json.loads(body)["error"]["type"] == "invalid_request_error"
        status, _, body = _http(port, "POST", "/v1/embeddings", {"input": "x"})
        assert "404" in status

        serve.delete("oai")
    finally:
        serve.shutdown()
        rt.shutdown()


def test_stop_strings_truncate():
    """Stop strings are applied at the text layer, spanning decode blocks."""
    from ray_tpu.llm.openai import _StopTruncator

    tok = Tokenizer()
    full = "abcSTOPdef"
    ids = tok.encode(full)
    tr = _StopTruncator(tok, ("STOP",))
    out = ""
    for tid in ids:  # worst case: one token per feed
        out += tr.feed([tid])
    out += tr.flush()
    assert out == "abc"
    assert tr.stopped

    # No stop present: everything (including held-back prefixes) flushes.
    tr2 = _StopTruncator(tok, ("XYZ",))
    out2 = "".join(tr2.feed([t]) for t in tok.encode("plain text X here"))
    out2 += tr2.flush()
    assert out2 == "plain text X here"
    assert not tr2.stopped
