"""Programs compiled inside the window per serving round: the program
registry's ``jit_compiles`` (its compile listener,
``repro/obs/timeplane.py``) differenced over the window, over the window's
rounds.  Each one is host time the round waits for; a program with every
shape warmed up reads 0.  Layer: serving loop and scheduler (host)."""


def read(ctx):
    n = ctx.counters.get("jit_compiles")
    if n is None or not ctx.rounds:
        return None
    return n / ctx.rounds
