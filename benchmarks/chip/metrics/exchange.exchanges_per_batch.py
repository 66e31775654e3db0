"""Exchanges between ranks per batch: the program's counter `exchanges`
(one per `routing.exchange`, times the trips of a loop around it) over
the traced window's batches (`progtrace.readings`)."""
from benchmarks.chip import progtrace


def read(ctx):
    if ctx.get("program") is None:
        return None
    return progtrace.readings(ctx["program"], ctx["batches"]).get(
        "exchange.exchanges_per_batch")
