"""One module per model architecture; a configuration file names its own
(``"arch"``), as it names its kind.

A module ``archs/<arch>.py`` holds everything that knows the model's shape,
so that the kinds, the hooks and the reference stay the same for every
architecture.  What a training cell calls on it:

- ``dims(cfg)``: the numbers the model's equations use, from the
  configuration file's dict (a flat dict of numbers; ``vocab`` is the
  vocabulary the tokens are drawn from);
- ``program_weights(dm, seed)``: the program's parameter tree for ``seed``,
  made on the device in one jitted call;
- ``slices(tree)``: (whole leaves, layer-stacked leaves) of a tree in the
  program's layout, each keyed by the reference's name; slice ``l`` of a
  stacked leaf ``name`` is the reference's ``layers.<l>.<name>``;
- ``reference_view(dm, tree)``: such a tree sliced into the reference's
  leaves;
- ``change_norms(dm, tree, seed)``: the norm of each reference leaf of
  ``tree`` less the weights ``seed`` made;
- ``loss_fn(dm, w, tokens, dtype)``: the plain reference's loss of the
  reference's leaves ``w`` on ``tokens`` (B, S), computed in ``dtype``;
- ``train_step_flops(dm, batch, seq_len)``: the model FLOPs of one step;
- ``program_fields(cfg, dm)``: the program's ``ModelConfig`` fields, and
  their values, that the configuration states;
- ``configured(model_config, dm)``: the program's ``model_config`` with the
  fields the benchmark sets on it;
- ``smoke(cfg)``: the configuration cut to a size the CPU runs in seconds.
"""
from __future__ import annotations

import importlib


class UnknownArch(RuntimeError):
    """A configuration names an architecture that has no module here."""


def load(cfg: dict):
    """The module of the architecture the configuration's ``arch`` names."""
    name = cfg.get("arch")
    if not isinstance(name, str) or not name.isidentifier():
        raise UnknownArch(f"configuration {cfg.get('name')!r} names no "
                          f"architecture module (\"arch\": {name!r})")
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise UnknownArch(f"configuration {cfg.get('name')!r} names arch "
                          f"{name!r}, and there is no chipbench/archs/"
                          f"{name}.py") from None
