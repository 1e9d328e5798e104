"""Seconds of one phase of the engine's own cold-start timeline
(/debug/engine, cold_start.phases)."""


def read(ctx, phase):
    p = (ctx.debug_engine.get("cold_start") or {}).get("phases", {}).get(phase)
    if not p or p.get("duration_s") is None:
        return None
    return float(p["duration_s"])
