"""What a configuration ``requires`` of the program, checked before set-up.

A configuration file may say::

    "requires": {"program": "<module>.<symbol>", "why": "..."}

``program(config_name)`` imports the symbol from the checkout the benchmark
runs in and, where that fails, ends the run with exit code 1 and the
configuration's own reason: the checkout cannot run this configuration.
The query references of such a configuration call it when they are loaded
(``harness.load_query``), which is before any data, session or compile.
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def program(config_name: str) -> None:
    with open(os.path.join(HERE, "configs", config_name + ".json")) as f:
        needs = json.load(f)["requires"]
    module, _, symbol = needs["program"].rpartition(".")
    try:
        getattr(importlib.import_module(module), symbol)
    except (ImportError, AttributeError) as e:
        raise SystemExit(
            f"{config_name} requires {needs['program']}, which this "
            f"checkout's program lacks ({e}): {needs['why']}")
