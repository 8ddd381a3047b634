"""Host seconds inside the FIRST call of each dispatch variant --
tracing, lowering, compile or cache read, loading the executable --
summed over the process: the counter
``route.dispatch.first_call_ms_total``, which no route resets.  The
window compiles nothing, so all of it is set-up.  None where the
program has no such counter."""


def read(ctx):
    ms = (ctx.get("registry") or {}).get(
        "route.dispatch.first_call_ms_total")
    return None if ms is None else ms / 1e3
