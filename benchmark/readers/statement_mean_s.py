"""Mean wall time of one statement's queries in the window."""


def read(run, statement):
    walls = [q["wall_s"] for q in run["window"]["queries"]
             if q["ok"] and q["statement"] == statement]
    if not walls:
        return None
    return sum(walls) / len(walls)
