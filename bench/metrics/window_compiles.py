"""Backend compiles and persistent-cache loads inside the window, from
JAX's ``/jax/core/compile/backend_compile_duration`` events.  It should
read 0: every shape is warmed up in set-up."""


def read(run):
    return run.window_compiles
