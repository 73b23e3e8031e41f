"""The cache rules' contract (PR 49): llm/cache_rules.py has one class a rule
for what a layer keeps of a sequence, and LLMEngine asks them. Each rule, on
the toy configuration its serving test builds, answers every question of the
contract, and every option a rule cannot serve is refused by the engine in
that rule's own sentence."""
import dataclasses
import importlib
import re

import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine, cache_rules

# rule -> the serving test whose CFG and ENGINE_KW have a layer of it
RULES = {"PagedRows": "test_llm_tp", "SlotRing": "test_window_moe", "SlotState": "test_solar_serving",
         "LatentRows": "test_latent_moe"}
OPTIONS = {"tensor_parallel > 1": dict(tensor_parallel=2), "prefix_cache": dict(prefix_cache=True),
           "chunked_prefill": dict(chunked_prefill=True)}  # True: a chunk of one page


def _toy(rule: str):
    """(cfg, engine kwargs) of ``rule``'s serving test; without held experts where it has a choice, so that
    tensor parallelism is the cache rule's to refuse and not the FFN's."""
    mod = importlib.import_module(RULES[rule])
    cfg = mod.CFG
    if cfg.experts_held and rule != "LatentRows":
        cfg = dataclasses.replace(cfg, n_experts=0, experts_held=0, expert_d_ff=0, n_shared_experts=0)
    return cfg, dict(mod.ENGINE_KW)


def _rule_of(eng, rule: str):
    return next(r for r in eng.rules if type(r).__name__ == rule)


@pytest.mark.parametrize("name", RULES)
def test_a_rule_answers_every_question_of_the_contract(name):
    cfg, kw = _toy(name)
    eng = LLMEngine(cfg, engine_config=EngineConfig(**kw))
    assert [r.name for r in eng.rules] == [k.name for k in cfg.kinds]  # one rule a kind, in the pools' order
    assert [r.sl.start for r in eng.rules] == [0] + [r.sl.stop for r in eng.rules[:-1]]
    rule = _rule_of(eng, name)
    assert isinstance(rule, cache_rules.CacheRule) and rule is eng._rule(rule.kind)
    # its pools: shapes, dtypes, bytes, and the shape a decode program carries each in
    pools = eng.cache[rule.sl]
    assert [(tuple(shape), np.dtype(spec[0] if spec else cfg.dtype)) for shape, _p, *spec in rule.pools()] == \
        [(pool.shape, pool.dtype) for pool in pools]
    assert eng.pool_bytes[rule.name] == sum(pool.nbytes for pool in pools)
    for pool in pools:
        carried = rule.in_pages(pool.shape)
        assert pool.reshape(carried).size == pool.size
        assert (carried == pool.shape) == (rule.tok_axis is None)
    # what the host tells a prefill of its slots, and the walk a decode step builds
    place = rule.place(np.arange(2))
    assert place is None or (place.dtype == np.int32 and place.shape == (2,))
    assert (len(eng._places(np.arange(2))) == 1) == any(r.place(np.arange(2)) is not None for r in eng.rules)
    assert rule.walk_key is None or rule.walk_key == (getattr(rule, "window", 0), rule.group)
    # what it counts: every key starts a step's record at zero
    eng.generate(np.arange(1, 20) % cfg.vocab_size, max_tokens=6)
    steps = eng.trace_snapshot()["steps"]
    counted = rule.block_counts(np.array([5, 17]), 4)
    assert all(isinstance(v, int) for v in counted.values())
    assert set(rule.zeroes) | set(counted) | set(rule.device_counts) <= set(steps[0])
    assert set(counted) | set(rule.device_counts) <= set(rule.zeroes) | {"live_pages", "grid_steps"}
    for key in (*rule.zeroes, *counted):
        assert any(s[key] for s in steps), key  # and a request moved it
    # what it can restore: the three methods of a rule with pages, or none of them and a sentence
    restores = [hasattr(rule, m) for m in ("read_pages", "write_pages", "tail_attend")]
    assert all(restores) or not any(restores)
    assert all(restores) == (rule.refuses("prefix_cache") is None) == (rule.refuses("chunked_prefill") is None)
    for other in eng.rules:
        assert rule.beside(other) is None  # the engine was made: its rules stand beside each other


@pytest.mark.parametrize("option", OPTIONS)
@pytest.mark.parametrize("name", RULES)
def test_the_engine_refuses_in_the_rules_own_sentence(name, option):
    """Asked of a rule made alone (``rule_for``: no engine), then of the engine
    with the option on: the sentence is the rule's, or the engine is made."""
    cfg, kw = _toy(name)
    ec = EngineConfig(**{**kw, "total_pages": 9})
    rules = [cache_rules.rule_for(cfg, kind, ec, first=0) for kind in cfg.kinds]
    why = next(r for r in rules if type(r).__name__ == name).refuses(option)
    on = {key: kw["page_size"] if key == "chunked_prefill" else value for key, value in OPTIONS[option].items()}
    config = EngineConfig(**kw, **on)
    if why is None:
        assert all(r.refuses(option) is None for r in rules)
        made = _rule_of(LLMEngine(cfg, engine_config=config), name)
        assert (made.mesh is not None) == (option == "tensor_parallel > 1")  # the rule was told the mesh it shards over
        return
    assert why.startswith(f"{option} is not written for ")
    with pytest.raises(ValueError, match=re.escape(why)):
        LLMEngine(cfg, engine_config=config)
