"""JIT / entry: XLA compilations inside the measured window, from JAX's
compile events. Every shape is warmed up before the window, so this
reads 0; a compile inside the window lands in some request's latency."""


def read(w):
    return float(w.compiles)
