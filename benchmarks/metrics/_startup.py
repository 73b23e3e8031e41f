"""What the readers of set-up's parts share: LLMServer.stats()["startup"], the
replica's own account of its constructor: three durations (`fetch_params_s`,
`engine_init_s`, `warmup_s`) and, since the PR that added them, the stamps
`init_began` (the process has its imports and its backend; the durations
follow) and `init_ended` (the constructor's last statement) on
time.monotonic(), the clock of the client's window on the same machine.
setup_before_replica_s reads `init_began`, setup_after_replica_s `init_ended`,
setup_weights_s and setup_warmup_s the durations. A program without the
stamps (the parent of that PR) reads as None: the four parts come in together
or not at all."""


def startup(ctx):
    s = (ctx.r.get("stats") or {}).get("startup")
    return s if s and s.get("init_began") is not None and s.get("init_ended") is not None else None
