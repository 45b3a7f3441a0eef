"""The benchmark's own code: what runs a cell and reduces it to numbers.
Nothing here imports the program under test or JAX (trace_reduce.py is the
one exception, and it runs in a child process after the server has gone)."""

import importlib.util


def load_module(path: str, name: str):
    """A file of the benchmark found by name (a configuration's reference,
    a metric's reader) as a module."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
