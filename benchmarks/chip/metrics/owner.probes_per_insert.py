"""Mean of the `probes` array that `hashtable.insert` returns, over the
window's inserts: slots each insert probed before it claimed one or gave
up."""


def read(ctx):
    n = ctx["counters"].get("insert_ops", 0)
    if not n:
        return None
    return ctx["counters"]["insert_probes"] / n
