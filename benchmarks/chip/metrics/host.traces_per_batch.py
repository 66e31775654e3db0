"""Jaxprs traced on the host per batch: the count of JAX's
`jaxpr_trace_duration` events inside the window, over its batches."""


def read(ctx):
    if not ctx.get("batches"):
        return None
    return ctx["compile_n"].get("/jax/core/compile/jaxpr_trace_duration",
                                0) / ctx["batches"]
