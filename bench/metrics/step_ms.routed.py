"""Device time of each routed step's program execution in the traced
window, mean over those steps and over chips, in ms. The i-th execution
of the step program is labelled with the i-th step's consensus bit."""


def read(ctx):
    t = ctx["trace"]
    steps, decs = t["step_s"], t["decisions"]
    if not steps or len(steps) != len(decs):
        return None
    xs = [s for s, d in zip(steps, decs) if d is False]
    return 1e3 * sum(xs) / len(xs) if xs else None
