"""setup_s (host clock): process start to the start of the measured
window: JAX and TPU init, codec warm-up, cluster start and health, the
mix's populate and failure, and its warm-up traffic."""


def read(w):
    return w.setup_s
