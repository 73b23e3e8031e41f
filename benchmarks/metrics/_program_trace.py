"""What the readers of the program's own record share. LLMServer.stats()["trace"]
(the harness keeps the whole of stats() in the run's record) holds the
finished requests' lifecycle records and the ended steps' phase records, each
in a bounded ring, with stamps on time.monotonic(): the client's window
(ctx.window) is on the same CLOCK_MONOTONIC. A program without the record (the
parent of the PR that added it) reads as None, and the metric is left out."""


def in_window(ctx, ring: str, stamp: str, pushed_at: str):
    """The records of `ring` whose `stamp` lies in the client's window; None
    without the record, or where the ring dropped records that may have been
    in the window. Records are pushed in the order of their `pushed_at`
    stamp (a request's `finished`, a step's `t`), so what a ring dropped is
    older than the oldest it still holds: if that one is older than the
    window's start, so is everything dropped."""
    tr = (ctx.r.get("stats") or {}).get("trace")
    if not tr:
        return None
    w0, w1 = ctx.window
    recs = tr[ring]
    if tr["dropped"][ring] and not (recs and recs[0][pushed_at] <= w0):
        return None
    return [r for r in recs if r.get(stamp) is not None and w0 <= r[stamp] < w1]


def request_gap_p50_ms(ctx, start: str, end: str, within: str):
    """Median of end - start over the finished requests whose `within` stamp
    lies in the window and that have both stamps."""
    recs = in_window(ctx, "requests", within, "finished")
    if recs is None:
        return None
    xs = [(r[end] - r[start]) * 1e3 for r in recs
          if r.get(start) is not None and r.get(end) is not None]
    return ctx.percentile(xs, 50) if xs else None
