"""Programs JAX traced and lowered inside the window (jax.monitoring's
``jaxpr_to_mlir_module`` events).  Every ProgramCache miss builds its
program through ``jax.jit(...).lower``, so the count holds the trainer's
misses; it also holds a lowering that the persistent cache then serves,
whose host time the window pays all the same."""


def read(ctx):
    return float(ctx["window"]["window_compiles"])
