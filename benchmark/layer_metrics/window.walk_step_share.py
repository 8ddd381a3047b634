"""Of the pointer-chase steps the traceback walks were budgeted
(``max_len - 4`` per executed wave), the share they ran
(``RouteResult.total_walk_steps`` over ``total_walk_budget``).  None
where the program's result carries no such fields."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    steps = getattr(routes[0], "total_walk_steps", None)
    budget = getattr(routes[0], "total_walk_budget", None)
    if steps is None or not budget:
        return None
    return 100.0 * steps / budget
