"""All-to-all device time in each routed step's program execution in the
traced window (``trace_reduce``'s ``a2a_s``: ops whose name holds
``all-to-all``), mean over those steps and over chips, in ms. The i-th
execution is labelled with the i-th step's consensus bit. None where the
trace holds no routed step."""


def read(ctx):
    t = ctx["trace"]
    a2a, decs = t.get("a2a_s"), t["decisions"]
    if not a2a or len(a2a) != len(decs):
        return None
    xs = [a for a, d in zip(a2a, decs) if d is False]
    return 1e3 * sum(xs) / len(xs) if xs else None
