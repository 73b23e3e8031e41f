"""Pages of the KV pool that slots hold, over the pages admission budgets
(every page but the dead one; the prefix cache's and the free ones are the
rest): each step's `pages_reserved`, taken once the step has admitted, over
the snapshot's `pages_total`. Mean over the steps that started in the window.
What a choice of max_slots and total_pages rests on: near 100 admission waits
for pages, far below it the pool holds memory no request reaches."""
from metrics._program_trace import in_window


def read(ctx):
    steps = in_window(ctx, "steps", "t", "t")
    total = ((ctx.r.get("stats") or {}).get("trace") or {}).get("pages_total")
    if not steps or not total or any("pages_reserved" not in s for s in steps):
        return None
    return 100.0 * sum(s["pages_reserved"] for s in steps) / len(steps) / total
