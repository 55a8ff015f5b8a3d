"""Device idle time while the driving thread was in ``chunk.decide``
(host_cond's consensus draw and its fetch), per step of the traced
window, mean over chips, in ms (``layer_reduce``)."""
import layer_reduce


def read(ctx):
    return layer_reduce.idle_ms(ctx.get("layers"), ("chunk.decide",))
