"""Share of the traced step's operation time that lies under one of the
program's phase scopes (phase_reduce.py).  The guard of the five metrics beside
it: a refactor that drops a scope from the step body shows here first."""

from phase_reduce import phases


def read(ctx):
    found = phases(ctx)
    return None if found is None else 100.0 * found["cover"]
