"""Share of the dense sink tables' entries that hold a real (wire cell
-> input pin -> SINK) hop: the gauge ``route.sink_pick.table_fill``,
set by ``build_planes_terminals`` where it builds ``uid_pcdel`` /
``uid_pcrank [U, P, C]``, P the most input pins and C the most distinct
wire cells of any one sink.  The sink pick forms B x S x P x C hops a
wave whatever the fill, so the empty share is work the widest sink
costs every other: a hard block's one-pin sinks beside a cluster's 40
equivalent inputs fill 1 / 40 of their rows.  None where the program
sets no such gauge."""


def read(ctx):
    fill = (ctx.get("registry") or {}).get("route.sink_pick.table_fill")
    return None if fill is None else 100.0 * fill
