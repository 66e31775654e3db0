"""1 minus the union of device-program intervals over the traced window.
Read only where the trace covers the whole window: a trace cut short by
a buffer drop covers part of one batch, whose idle share is not the
window's."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or tr.dropped_s:
        return None
    return tr.idle_share
