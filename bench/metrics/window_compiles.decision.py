"""Programs compiled or loaded from the persistent cache inside the
window (jax.monitoring events): 0 when set-up warmed every shape."""


def read(run: dict):
    return run["window_compiles"]
