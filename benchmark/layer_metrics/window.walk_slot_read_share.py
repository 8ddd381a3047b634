"""Of the walk slots a route's waves were budgeted (``max_len - 4`` a
wave: ``RouteResult.total_walk_budget``), the share the waves' two
element scatters (tree grow, path assembly) READ
(``RouteResult.total_walk_slots_read``): a wave that scatters its walks'
steps in whole chunks reads the chunks that hold its longest KEPT walk,
one that scatters every slot the budget, so 100% is a program that cuts
nothing and the floor is the steps of the kept walks
(``window.walk_step_share`` where no walk overruns its budget; a walk
that does is not kept, and the share can read under it).  The run's
first timed route.  None where the program's result carries no such field
(the parent's) or no windowed wave ran."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    read_ = getattr(routes[0], "total_walk_slots_read", None)
    budget = getattr(routes[0], "total_walk_budget", None)
    if read_ is None or not budget:
        return None
    return 100.0 * read_ / budget
