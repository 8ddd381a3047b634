"""Of the sweeps a route executed, the share that improved no distance
(fixpoint discovery and ceiling overhead)."""


def read(ctx):
    routes = ctx.get("routes")
    if not routes:
        return None
    r = routes[0]
    total = r.total_relax_steps_useful + r.total_relax_steps_wasted
    return 100.0 * r.total_relax_steps_wasted / total if total else None
