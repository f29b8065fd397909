"""Plain references, one module per model family, named by a
configuration's "reference" key."""

import importlib


def load(name: str):
    return importlib.import_module(f"bench.references.{name}")
