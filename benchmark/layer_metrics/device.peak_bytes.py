"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device after
the window."""


def read(ctx):
    return ctx.get("memory_peak_bytes") or None
