"""Critical-path delay of the routed design (``flow.crit_path_delay``,
the program's STA on the routed sink delays, which ``correct`` holds to
the float64 sums along the routed trees): the number BASELINE.md's
north star is written in."""


def read(ctx):
    return ctx.get("crit_path_ns")
