"""Programs the replica's JAX backend compiled inside the client's window,
from the program's own counter (accel/device.compile_events, a stamp at the
end of each compile). Should be 0: every shape is warmed before the window."""


def read(ctx):
    tr = (ctx.r.get("stats") or {}).get("trace")
    if not tr or "compiles" not in tr:
        return None
    w0, w1 = ctx.window
    stamps = [t for t, _seconds in tr["compiles"]]
    if tr["compiles_total"] > len(stamps) and not (stamps and stamps[0] <= w0):
        return None  # the counter's ring dropped stamps that may lie in the window
    return float(sum(w0 <= t < w1 for t in stamps))
