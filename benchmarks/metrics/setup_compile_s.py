"""Backend-compile seconds of the programs built during set-up that were not
loads from the persistent cache (metrics.CompileCounter over jax.monitoring,
until the first chunk is read back)."""


def read(run):
    return run["summary"].get("setup_compile_s")
