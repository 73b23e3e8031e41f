"""A program with more than one Mosaic kernel (harness/xplane.py `reduce`'s
`kernels`, harness/context.py `kernel_of` and `traced_decode_steps`), on a
hand-written trace: selftest_data/trace_two_kernels.json, whose decode program
runs 2 steps of 4 layers with the attention kernel in every layer (3,000 ns a
call) and a grouped matmul in every second one (4,000 ns), and whose prefill
program runs one flash kernel (5,000 ns). The decode program's second kernel
is what the three serve cells' programs do not have: for them `kernels` holds
one name a program and every reader reads what it read."""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import cellspec, xplane  # noqa: E402
from harness.context import Context  # noqa: E402

DECLARING = os.path.join(os.pardir, "selftest_data", "two_kernel_decoder")
NS = 1e-9


def _trace(name):
    with open(os.path.join(BENCH_DIR, "selftest_data", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce(_trace("trace_two_kernels"))


def _context(reduced, architecture=None):
    config = {"num_hidden_layers": 4}
    if architecture:
        config["architecture"] = architecture
    return Context({"kind": "serve", "seconds": 51.0, "config": config, "traffic": {}, "traced": reduced}, 1)


KNOWN = {  # program -> kernel -> (ns, calls)
    "jit__decode_impl": {"paged_attn.6": (8 * 3000, 8), "gmm.9": (4 * 4000, 4)},
    "jit__prefill_batch_impl": {"flash_attn.7": (5000, 1)},
}


@pytest.mark.parametrize("program,kernel", [(p, k) for p, ks in KNOWN.items() for k in ks])
def test_each_kernel_is_kept_under_its_instructions_name(reduced, program, kernel):
    ns, calls = KNOWN[program][kernel]
    assert set(reduced["kernels"][program]) == set(KNOWN[program])
    assert reduced["kernels"][program][kernel]["calls"] == calls
    assert reduced["kernels"][program][kernel]["seconds"] == pytest.approx(ns * NS, rel=1e-12)


@pytest.mark.parametrize("trace", ("trace_two_kernels", "trace_small"))
def test_a_programs_sum_is_its_kernels_added_up(trace):
    out = xplane.reduce(_trace(trace))
    assert set(out["kernels"]) == set(out["kernel"]) and out["kernel"]
    for program, total in out["kernel"].items():
        for key in ("seconds", "calls"):
            assert total[key] == pytest.approx(sum(k[key] for k in out["kernels"][program].values()), rel=1e-12)


def test_the_keys_the_parent_had_read_what_they_read(reduced):
    """Worked out by hand from the trace's description (its "what")."""
    assert reduced["kernel"]["jit__decode_impl"]["calls"] == 12
    assert reduced["kernel"]["jit__decode_impl"]["seconds"] == pytest.approx(40_000 * NS, rel=1e-12)
    assert reduced["module_s"] == pytest.approx({"jit__decode_impl": 80_000 * NS, "jit__prefill_batch_impl": 15_000 * NS})
    assert reduced["module_runs"] == {"jit__decode_impl": 1, "jit__prefill_batch_impl": 1}
    # busy: 8 x 5,000 + 4 x 4,000 of the decode block, 15,000 of the prefill; the window is 0..95,000
    assert reduced["busy_s"] == pytest.approx(71_000 * NS, rel=1e-12) and reduced["window_s"] == pytest.approx(95_000 * NS)
    assert dict(reduced["idle_gaps"]) == pytest.approx({"inside_engine.step": 24_000 * NS})
    assert reduced["device_ops"][0][0] == "jit__decode_impl/paged_attn.6"


@pytest.mark.parametrize("name,ns,calls", [(None, 40_000, 12), ("paged_attn", 24_000, 8), ("gmm", 16_000, 4),
                                           ("gmm.9", 16_000, 4), ("", 40_000, 12)])
def test_kernel_of_all_of_a_programs_kernels_or_those_named(reduced, name, ns, calls):
    k = _context(reduced).kernel_of("_decode_impl", name)
    assert k["calls"] == calls and k["seconds"] == pytest.approx(ns * NS, rel=1e-12)


def test_kernel_of_a_name_the_program_does_not_have_is_none(reduced):
    ctx = _context(reduced)
    assert ctx.kernel_of("_decode_impl", "flash_attn") is None
    assert ctx.kernel_of("_prefill_batch_impl", "flash_attn")["calls"] == 1
    assert ctx.kernel_of("no_such_program") is None and ctx.kernel_of("no_such_program", "gmm") is None
    older = dict(reduced)  # a record reduced before `kernels` was there
    del older["kernels"]
    assert _context(older).kernel_of("_decode_impl", "gmm") is None
    assert _context(older).kernel_of("_decode_impl")["calls"] == 12


def test_decode_steps_follow_the_declared_kernel(reduced):
    """2 steps ran. Counted from every Mosaic call over the layers, which is
    right for a program with one kernel a layer, the trace's 12 calls read 3;
    an architecture that declares its kernels is counted from the first."""
    assert cellspec.decode_kernels({"architecture": DECLARING, "num_hidden_layers": 4}) == {"paged_attn": 4, "gmm": 2}
    assert _context(reduced, DECLARING).traced_decode_steps() == 2.0
    assert _context(reduced).traced_decode_steps() == 3.0


def test_the_dense_architecture_declares_no_kernels_and_reads_the_quotient_it_read():
    for name in ("internlm2-1.8b", "mistral-7b-v0.3"):
        with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
            config = json.load(f)
        assert cellspec.decode_kernels(config) is None
        layers = config["num_hidden_layers"]
        traced = {"module_s": {"jit__decode_impl": 2.0}, "module_runs": {"jit__decode_impl": 10},
                  "kernel": {"jit__decode_impl": {"seconds": 0.5, "calls": 10.0 * layers}},
                  "kernels": {"jit__decode_impl": {"shard_map.141": {"seconds": 0.5, "calls": 10.0 * layers}}}}
        ctx = Context({"kind": "serve", "seconds": 51.0, "config": config, "traffic": {}, "traced": traced}, 1)
        assert ctx.traced_decode_steps() == 10.0


@pytest.mark.parametrize("metric,known", [("decode_ms_per_step", 80_000 * NS / 2 * 1e3),
                                          ("paged_attn_time_share", 100.0 * 40_000 / 71_000)])
def test_the_older_readers_on_the_two_kernel_trace(reduced, metric, known):
    """decode_ms_per_step counts steps by the declaration; paged_attn_time_share
    still reads the program's sum (a program with a second kernel brings a
    reader that names its own: ctx.kernel_of("_decode_impl", "paged_attn"))."""
    assert cellspec.load_metric(metric)(_context(reduced, DECLARING)) == pytest.approx(known, rel=1e-9)
