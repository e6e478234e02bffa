"""Backend compiles inside the window (jax.monitoring's
backend_compile_duration events). Every step shape is compiled in set-up,
so this reads 0 unless the engine met a shape the warm-up did not."""


def read(run):
    w0, w1 = run.window
    return float(sum(1 for t in run.compiles_rel if w0 <= t < w1))
