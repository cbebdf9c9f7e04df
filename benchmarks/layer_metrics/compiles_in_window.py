"""Engine, host half: programs JAX compiled (or loaded) inside the window;
every shape is warmed up before it, so this is 0 unless a shape escaped the
prewarm grid."""


def read(ctx):
    return len(ctx["compiled_in_window"])
