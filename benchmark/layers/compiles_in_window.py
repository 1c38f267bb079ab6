"""Backend compile requests JAX made inside the measured window (its
backend_compile_duration event).  Must be 0."""


def read(obs):
    return obs.compiles_in_window
