"""A number the harness took itself, by its key: ``setup_s`` (process start
to window start), ``first_call_s`` (sum of the statements' first calls in
the process: plan, compile or cache read, run)."""


def read(run, key):
    return run[key]
