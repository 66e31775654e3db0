"""Share of the window that JAX spent tracing, lowering and compiling
(or fetching from the compile cache) on the host: the summed durations of
JAX's `jaxpr_trace_duration`, `jaxpr_to_mlir_module_duration` and
`backend_compile_duration` events inside the window, over its seconds."""

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


def read(ctx):
    if not ctx.get("window_s"):
        return None
    return sum(ctx["compile_s"].get(e, 0.0) for e in EVENTS) / \
        ctx["window_s"]
