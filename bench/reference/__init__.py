"""Plain references, one module per model family. A configuration file
names its own (``"reference": "<module>"``); ``for_config`` finds it."""
import importlib
import re

_NAME = re.compile(r"^[A-Za-z0-9_]+$")


def for_config(config: dict):
    name = config.get("reference")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"reference module: {name!r}")
    return importlib.import_module(f"bench.reference.{name}")
