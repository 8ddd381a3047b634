"""Of the distance elements dense sink picks read in a route's waves
(B x S x C a wave: every sink slot of the batch, whatever it holds),
the share the route's picks read (``RouteResult.total_sink_reads`` over
``total_sink_reads_dense``): a wave that lists its LIVE sink slots --
real sinks of nets being routed that no wave has reached -- reads the
list's M x C, one on the dense rung all B x S x C, so 100% is a program
that compacts nothing and the lower the share the less the pick reads
to throw away.  The run's first timed route.  None where the program's
result carries no such fields (the parent's) or no windowed wave ran."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    reads = getattr(routes[0], "total_sink_reads", None)
    dense = getattr(routes[0], "total_sink_reads_dense", None)
    if reads is None or not dense:
        return None
    return 100.0 * reads / dense
