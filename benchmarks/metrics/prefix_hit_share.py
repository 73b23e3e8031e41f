"""Admissions served from the prefix cache (exact or partial), over all
admissions, from engine.prefix_cache_stats."""


def read(ctx):
    pc = (ctx.r.get("stats") or {}).get("prefix_cache")
    if not pc:
        return None
    n = pc["hits"] + pc["partial_hits"] + pc["misses"]
    return 100.0 * (pc["hits"] + pc["partial_hits"]) / n if n else None
