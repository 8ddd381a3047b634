"""Net routes a batch: over the ``RouteResult.stats`` rows of the run's
first timed route, the sum of ``net_routes`` (nets ripped up and
re-routed, ``nroutes`` of the window programs' packed ``scal``) over the
sum of ``batches`` (``nexec``: the batch groups that executed).  Of a
batch's B slots, how many worked: in the tail of a full rebuild the
window program packs each batch from the nets that need a re-route,
and this is the counter that says how often that engages.  None where there are no rows or no batch
ran."""


def read(ctx):
    routes = ctx.get("routes")
    rows = routes[0].stats if routes else None
    batches = sum(s.batches for s in rows) if rows else 0
    if not batches:
        return None
    return sum(s.net_routes for s in rows) / batches
