"""Device values the program read back on the host per batch: its
counter `host_reads` over the traced window's batches
(`progtrace.readings`)."""
from benchmarks.chip import progtrace


def read(ctx):
    if ctx.get("program") is None:
        return None
    return progtrace.readings(ctx["program"], ctx["batches"]).get(
        "host.reads_per_batch")
