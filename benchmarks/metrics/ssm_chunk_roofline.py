"""The least time the chip could take for the state-space layers' chunked calls
in the traced prefills (the chunked form's matrix products over peak compute,
or x, B, C read and y written once over peak bandwidth, whichever is more),
over the time they took. A call takes one prompt padded to its bucket: the
trace says how many calls, the replica's counters around it how many padded
tokens a prefilled request."""
from harness.cellspec import architecture


def read(ctx):
    k = ctx.kernel_of("_prefill_batch_impl", "ssd_chunk")
    needs_of = getattr(architecture(ctx.config), "ssd_chunk_needs", None)
    if not k or not k["seconds"] or needs_of is None:
        return None
    a, b = ctx.traced["counters_before"], ctx.traced["counters_after"]
    requests = b.get("prefill_requests", 0) - a.get("prefill_requests", 0)
    if requests <= 0:
        return None
    a_request = (b["prefill_padded_tokens"] - a["prefill_padded_tokens"]) / requests
    needs = needs_of(ctx.config, padded_tokens=a_request * k["calls"])  # a call: one layer of one request
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
