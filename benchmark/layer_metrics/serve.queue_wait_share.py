"""Sum of ``queue_wait`` over sum of ``e2e`` in the daemon's SLO
waterfalls (``obs/slo.py``, the server's clock) of the window's jobs:
the share of a job's life spent waiting for its first slice."""


def read(ctx):
    slo, ids = ctx.get("slo"), ctx.get("window_job_ids")
    if not slo or not ids:
        return None
    falls = [w for w in slo["waterfalls"] if w["job_id"] in ids]
    e2e = sum(w["e2e_us"] for w in falls)
    if not e2e:
        return None
    return 100.0 * sum(w["stages_us"]["queue_wait"] for w in falls) / e2e
