"""The latest any submission left against its due time: a starved
generator must not read as a fast server."""


def read(ctx):
    late = ctx.get("gen_late")
    return max(late) if late else None
