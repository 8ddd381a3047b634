"""90th percentile of the client-side job latencies (due time to
terminal record seen) over the window's done jobs.  A per-layer number
until a window finishes enough jobs for a tail to stand end to end."""


def read(ctx):
    lats = ctx.get("latencies")
    if not lats or len(lats) < 10:
        return None
    print(f"serve.job_p90_s: {len(lats)} samples", flush=True)
    return sorted(lats)[min(len(lats) - 1, int(0.9 * len(lats)))]
