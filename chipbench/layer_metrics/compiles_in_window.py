"""Backend compiles (or loads from the compile cache) between the
window's first job and its last, counted by the harness's
``jax.monitoring`` listener on ``backend_compile_duration``."""


def read(run: dict):
    return run["compiles"]
