"""Model configurations and their plain references.

`<config>.json` holds one configuration as it is run; its "reference" key
names the module here that holds the plain reference of its architecture
family.  Everything that belongs to one family lives in that module: the
sizes the work counts use (`dims`), the layout of its seeded weights
(`layer_layout`, `global_layout`), the map from those weights to the
program's parameter tree (`program_params`), what the program's registry
entry has to state (`program_mismatch`), and the reference forward pass
(`final_hidden`, `head_readings`).  A new family is one new module.
"""
from __future__ import annotations

import importlib


def reference(spec: dict):
    """The reference module a configuration file names."""
    return importlib.import_module(f"bench.configs.{spec['reference']}")
