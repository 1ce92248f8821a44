"""The model family of a configuration: what the benchmark knows of one
architecture, found by the config file's ``model_type``.

A family is the package ``chipbench/families/<model_type>/``.  Its
``__init__`` exposes:

* ``Dims``: ``Dims.from_config(config)`` gives the sizes the family's
  functions take, a frozen, hashable dataclass (it is a static argument of
  jitted functions) with at least ``vocab``, the range the traffic draws
  token ids from (``traffic.schedule``, ``run.warm_shapes``);
* ``model_config(config)``: the serving program's ``ModelConfig``;
* ``make_params(config, seed, cfg)``: the program's parameter tree, made on
  the device in one jitted call, in the served type;
* ``served_gaps(dims, seed, pairs)`` and ``control_gaps(dims, seed,
  pairs)``: the plain reference's gap of each served token, and the
  control's, per ``(prompt, served tokens)`` pair (``run.run_cell``);
* ``flops``: a module with ``decode_flops``, ``prefill_flops``,
  ``decode_attn_work`` and ``token_flops``, taking ``dims`` first (the
  per-layer readers in ``chipbench/metrics/``).

Weights are drawn from ``families.qwen3.weights.root_key(seed)``, so that
the program's weights and the reference's are the same numbers.  A new
architecture arrives as a new directory: it may import what it shares from
a sibling (``from families.qwen3 import reference as q3``) and edits no
existing file.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

FAMILIES = Path(__file__).resolve().parent / "families"


def load(config: dict, root: Path = FAMILIES):
    """The family package of ``config["model_type"]``, from ``root``."""
    kind, name = config["model_type"], f"families.{config['model_type']}"
    present = sorted(p.parent.name for p in root.glob("*/__init__.py"))
    if kind not in present:
        raise SystemExit(f"no benchmark family for model_type {kind!r}; "
                         f"families present: {', '.join(present)}")
    if root == FAMILIES:
        return importlib.import_module(name)
    spec = importlib.util.spec_from_file_location(
        name, root / kind / "__init__.py",
        submodule_search_locations=[str(root / kind)])
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
