"""Plain references, one module per model family. A configuration file
names its own (``"reference": "<module>"``); ``for_config`` finds it.

What the harness reads from a module (``dense_decoder.py`` has each):
  param_shapes(config)   leaf path -> shape, in the program's layout;
  make_step, EINSUMS, leaf_norms
                         the float32 step that ``bench.check`` follows,
                         and its control;
  flops_per_token(config, seq_len)
                         the model FLOPs of one trained token, by the rule
                         that ``bench.flops`` states;
  WIDTHS, APPLIED        program field -> configuration key: the widths
                         that the program's configuration must have, and
                         what the file sets in it (dotted paths reach into
                         nested blocks, "moe.top_k")."""
import importlib
import re

_NAME = re.compile(r"^[A-Za-z0-9_]+$")


def for_config(config: dict):
    name = config.get("reference")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"reference module: {name!r}")
    return importlib.import_module(f"bench.reference.{name}")
