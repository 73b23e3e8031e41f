"""Device time of the prefill programs, over the traced window."""


def read(ctx):
    if not ctx.traced:
        return None
    secs = sum(s for name, s in ctx.traced["module_s"].items() if "prefill" in name)
    return 100.0 * secs / ctx.traced["window_s"]
