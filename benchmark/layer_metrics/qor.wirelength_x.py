"""Routed wirelength over the serial router's (``native/serial_route.cc``,
defaults, run after the window) on the same placed problem at the same
width.  A count, exact for a fixed problem; ``correct`` holds it under
the configuration's 1.10."""


def read(ctx):
    return ctx.get("wirelength_x")
