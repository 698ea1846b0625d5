"""Largest over mean load of the held experts: the program's
``load_max_over_mean`` counter (``moe_stats`` -> telemetry ``moe`` rows),
mean over layers and over the window's logged steps. 1 is perfect balance;
the grouped product's tiles fill worse as it grows. Nothing where the
program writes no such rows."""


def read(ctx):
    counters = getattr(ctx["family"], "moe_counters", lambda ctx: None)(ctx)
    return counters["load_max_over_mean"] if counters else None
