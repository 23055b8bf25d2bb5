"""Families of configurations, one module each; a configuration file names
its family under ``family`` and the harness reaches everything that is
specific to it through that module (``message_passing.py`` has the list of
entry points). A new family is a new file here."""

import importlib


def load(name: str):
    return importlib.import_module(f"families.{name}")
