"""Plain references, one module per architecture; a configuration file
names its own under ``"reference"``."""

import importlib


def load_reference(m: dict):
    """The reference module that configuration ``m`` names."""
    return importlib.import_module(f"benchmarks.chip.reference.{m['reference']}")
