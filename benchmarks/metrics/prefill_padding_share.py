"""The share of the prefill programs' rows that were padding: 100 x (1 -
`prefill_tokens` / `prefill_padded`) over the steps that started in the
window, where `prefill_tokens` are the prompts' own tokens in a step's prefill
groups and `prefill_padded` the rows their programs ran over (group size x
bucket). 25-28% on a ladder of buckets that doubles under log-normal lengths;
a rung between doublings brings it to 14-19%. A program whose step records
lack the counters (before the engine counted them) reads as None, as does a
window without a prefill."""
from metrics._program_trace import in_window


def read(ctx):
    steps = in_window(ctx, "steps", "t", "t")
    if not steps or any("prefill_padded" not in s or "prefill_tokens" not in s for s in steps):
        return None
    padded = sum(s["prefill_padded"] for s in steps)
    return 100.0 * (1.0 - sum(s["prefill_tokens"] for s in steps) / padded) if padded else None
