"""layer: device pass.  source: program_counter
(scheduler_jax_compile_seconds_total at the window's close: every XLA
program the serving process built or loaded, from jax.monitoring).  moves:
setup_s.  All of it is set-up: a window compiles nothing."""


def read(ctx):
    return ctx.after.get("scheduler_jax_compile_seconds_total")
