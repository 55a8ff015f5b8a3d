"""Device time under the program's ``moe`` name scope in each routed
step's program execution, mean over those steps and over chips, in ms
(``layer_reduce``). The i-th execution is labelled with the i-th step's
consensus bit."""
import layer_reduce


def read(ctx):
    return layer_reduce.scope_ms(ctx.get("layers"), "moe",
                                 ctx["trace"]["decisions"], dropped=False)
