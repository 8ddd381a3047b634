"""Of the window programs a route dispatched (one a populated rung:
``route.mis_colors.calls_total``), the share whose conflict colouring
ran its FULL form (``route.mis_colors.full_total``: the node-indexed
table, the gather of the whole path store and the scatter the compiler
lowers as a sort of as many indices), and neither skipped the colouring
because the host reads the colours of a window's last rung alone
(``.skipped_total``) nor built the conflict matrix from the short list
of overused nodes by dense compares (``.short_total``).  100% is a
program that colours every rung the dear way.  The registry is the
process's and is not reset between routes; the routes of a run are
identical, so the share over all of them is one route's.  None where
the program counts no forms (the parent's) or dispatched nothing."""


def read(ctx):
    reg = ctx.get("registry") or {}
    calls = reg.get("route.mis_colors.calls_total")
    forms = [reg.get(f"route.mis_colors.{form}_total")
             for form in ("skipped", "short", "full")]
    if not calls or all(f is None for f in forms):
        return None
    return 100.0 * (forms[2] or 0) / calls
