"""engine.host_ms_per_round: window time outside the engine's
block_until_ready-bracketed chunk dispatches (its RoundTelemetry
'measured' records), per round: staging, flush and the host loop."""


def read(ctx):
    recs = ctx["telemetry"]
    if not recs:
        return None
    dispatch = sum(r.dispatch_seconds for r in recs)
    return 1e3 * (ctx["window_s"] - dispatch) / ctx["rounds"]
