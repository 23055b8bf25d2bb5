"""Plain references, one module per model family; found by the name a
configuration file gives under ``reference``."""

import importlib


def conv_for(name: str):
    return importlib.import_module(f"reference.{name}").conv
