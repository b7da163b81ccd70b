"""setup_s (s): process start to a warmed engine holding the benchmark's
weights: imports, engine build, every serving shape compiled or loaded
from the persistent cache, the weights made and placed.  Host clock."""


def read(ctx):
    return ctx.setup_s
