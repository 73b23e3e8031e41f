"""A fixture of the self-test and of tests/test_kernels.py, not an architecture
of the benchmark: what an architecture's file declares when its decode program
calls more than one Mosaic kernel. Here, as selftest_data/trace_two_kernels.json
records it: the paged attention kernel in every layer and a grouped matmul in
every second one."""


def decode_kernels(model: dict) -> dict:
    """{a fragment of the kernel's name in the trace: its calls a decode step};
    decode steps are counted from the first (README, "An architecture")."""
    layers = model["num_hidden_layers"]
    return {"paged_attn": layers, "gmm": layers // 2}
