"""Executables built or loaded while the window was open
(``jax.monitoring``, counted by the benchmark). Anything but 0 also makes
the run not ``correct``."""


def read(run):
    return float(run.compiles_in_window)
