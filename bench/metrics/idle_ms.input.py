"""Device idle time while the driving thread waited for the prefetcher's
batch (``prefetch.wait``) or put it on the device (``chunk.put``), per
step of the traced window, mean over chips, in ms (``layer_reduce``)."""
import layer_reduce


def read(ctx):
    return layer_reduce.idle_ms(ctx.get("layers"), ("prefetch.wait",
                                                    "chunk.put"))
