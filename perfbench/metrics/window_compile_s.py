"""layer: device pass.  source: program_counter
(scheduler_jax_compile_seconds_total's delta over the window: every XLA
program the serving process built or loaded inside it, from
jax.monitoring).  moves: pods_per_s.  Seconds of compile inside the
window: a window is meant to compile nothing, and `compiled_in_window` in
the timeline only counts programs and warns; this is what they cost.  0.0
is the sound reading; a program without the counter reports nothing."""

KEY = "scheduler_jax_compile_seconds_total"


def read(ctx):
    if KEY not in ctx.after or KEY not in ctx.before:
        return None
    return ctx.delta(KEY)
