"""Mean time inside ``Session.sql(text)``, parse and logical plan, of the
window's queries. Overrides, optimiser and physical planning run inside
``collect()`` and are not in this number."""


def read(run):
    plans = [q["plan_s"] for q in run["window"]["queries"] if q["ok"]]
    if not plans:
        return None
    return 1e3 * sum(plans) / len(plans)
