"""Executables the backend built or fetched inside the window, from
``jax.monitoring`` (the harness counts them). Anything above 0 means a shape
was not warmed and its compile time sits in the tails."""


def read(stats, spans, trace, cell):
    return cell["window"]["compiles"]
