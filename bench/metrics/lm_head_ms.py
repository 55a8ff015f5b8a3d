"""Device time under the program's ``lm_head`` name scope in each step's
program execution, mean over the steps and over chips, in ms
(``layer_reduce``)."""
import layer_reduce


def read(ctx):
    return layer_reduce.scope_ms(ctx.get("layers"), "lm_head")
