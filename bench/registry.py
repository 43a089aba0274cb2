"""Files of the benchmark found by name, and the error that stops a run."""

import importlib.util
import sys
from pathlib import Path


class BenchError(SystemExit):
    """A run that cannot measure: it prints no result and exits non-zero."""

    def __init__(self, message: str):
        print(f"bench: {message}", file=sys.stderr, flush=True)
        super().__init__(2)


def load_module(path: Path, what: str):
    """The Python file ``path``, loaded as a module; ``what`` names it in
    the error when there is no such file."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    if spec is None or not path.exists():
        raise BenchError(f"{what}: no module {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
