"""All the time of the window over all the queries it completed."""


def read(run):
    done = [q for q in run["window"]["queries"] if q["ok"]]
    if not done:
        return None
    return run["window"]["seconds"] / len(done)
