"""Process start to window start: cluster boot, JAX start-up, compiling
or loading the cell's programs, warm-up and preload."""


def read(params: dict, run) -> float | None:
    return run.setup_s
