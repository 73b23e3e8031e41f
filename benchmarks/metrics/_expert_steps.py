"""What the readers of the program's expert counters share: the step records
of LLMServer.stats()["trace"] that started in the client's window and ran a
decode block, each with `block` (its steps) and, since the PR that added held
experts, `expert_pairs` (routed (token, expert) pairs that landed on held
experts) and `expert_tiles` (the grouped matmul's live tiles, each of which
reads its expert's matrices), both summed over the block's steps and the
routed layers. A program whose records lack a counter (the parent of that PR)
reads as None."""
from metrics._program_trace import in_window


def a_step_and_layer(ctx, counter):
    """Mean of `counter` in one routed layer of one decode step."""
    steps = [s for s in in_window(ctx, "steps", "t", "t") or [] if s.get("block")]
    if not steps or any(counter not in s for s in steps):
        return None
    routed_layers = ctx.config["num_hidden_layers"] - ctx.config["first_k_dense_replace"]
    return sum(s[counter] for s in steps) / (sum(s["block"] for s in steps) * routed_layers)
