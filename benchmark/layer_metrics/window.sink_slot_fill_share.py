"""Share of the sink slots a route's windows dispatched that hold a
real sink: the counter ``route.fanout.sinks_dispatched_total`` over
``route.fanout.sink_slots_dispatched_total`` (per net x window: the
net's sinks, and the width of the fanout class its batch is compiled
at; counted where the window driver builds its dispatch, beside
``route.crop.*``).  Every wave of a batch gathers, sorts and walks over
B x S slots whatever they hold, so the empty share is what the widest
net of a class costs every other net of it: a program whose tables are
dense in the widest net of the circuit reads mean fanout over Smax (2%
where sixteen nets have 200 sinks and the rest three), one that routes
in fanout classes the mean over each class's own width.  The registry
is the process's and the routes of a run are identical, so the ratio
over a run is one route's.  None where the program has no such counter
or dispatched nothing."""


def read(ctx):
    reg = ctx.get("registry") or {}
    sinks = reg.get("route.fanout.sinks_dispatched_total")
    slots = reg.get("route.fanout.sink_slots_dispatched_total")
    if sinks is None or not slots:
        return None
    return 100.0 * sinks / slots
