"""Model families: what varies by architecture, one module each.

A configuration file (``bench/configs/<name>.json``) names its family
under the key ``"family"``, and the family is the module
``bench.families.<family>``: adding an architecture is adding its file,
there is no table to edit.  A family module supplies:

- ``spec(c)``: the configuration file's dict as a frozen, hashable spec
  (a ``bench.model.ModelSpec`` with the family's own sizes), read under
  the source's key names;
- ``program_config(spec)``: the program's ``ModelConfig``, its layer
  pattern included (the weight generator stacks by it);
- ``top_leaves(spec)`` and ``layer_leaves(spec, layer)``: the name, shape
  and init of each leaf, per layer index, since kinds can differ within
  the pattern's period;
- ``layer(spec, x, w, layer, quant)``: the plain reference's float32
  equations of layer ``layer`` over one padded sequence;
- ``matmul_flops_per_token(spec)``, ``attn_flops(spec, n, start)``,
  ``attn_bytes(spec, n, start)`` and ``head_flops(spec)``: the useful
  work, summed over the family's own layers (``bench/work.py``).
"""
from __future__ import annotations

import importlib
import re


def load(name, where):
    """The family module that a configuration file names; ``where`` is
    the file, for the message when the key is missing or names no
    module."""
    if name is None:
        raise SystemExit(f'bench: {where} has no "family" key; it names '
                         f"the module bench/families/<family>.py that "
                         f"describes the model")
    full = f"{__name__}.{name}"
    if re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", str(name)):
        try:
            return importlib.import_module(full)
        except ModuleNotFoundError as e:
            if e.name != full:       # the family's own import failed
                raise
    raise SystemExit(f'bench: {where}: "family" {name!r} names no module '
                     f"bench/families/{name}.py")


def of(spec):
    """The family module of a spec made by ``load(...).spec``."""
    return importlib.import_module(f"{__name__}.{spec.family}")
