"""Plain references, independent of the system under test: they import
nothing of it and take nothing it made.

A configuration names its reference by its ``system`` key: the module
``bench/reference/<system>.py``, which :func:`find` loads.  A served
model's reference provides

* ``program_params(jax, conf, seed, *, padded_vocab, dtype)``: every
  weight drawn from the seed on the device in one jitted call, in the
  served ``dtype``, laid out as the system under test takes them (the
  embedding table padded to ``padded_vocab`` rows);
* ``forward_logits(jax, conf, seed, seqs, rows, *, served_dtype,
  quant=None)``: the plain forward's float32 logits over the published
  vocabulary, one numpy array ``(len(rows[i]), V)`` per sequence of
  ``seqs`` at the positions ``rows[i]``, from the same weights;
  ``quant="fp8"`` gives its control's (weights rounded to float8, one
  precision step below the served bfloat16);
* ``served_gaps(ref_logits, tokens)``: how far each served token's
  reference logit lies below the best at its position;

and may provide ``check_config(cfg, conf)``, which raises where the
system's ``ModelConfig`` departs from the configuration in what belongs
to that system alone (the sizes every decoder states are checked by
``bench.drivers.serve_common.model_config``)."""
from __future__ import annotations

import importlib
import re


def find(system: str):
    """The reference module of ``system``; a ``system`` with no module
    fails with the name of the file it would be."""
    if not isinstance(system, str) or not re.fullmatch(r"[A-Za-z_]\w*",
                                                       system):
        raise ValueError(f"configuration system {system!r} is not a module "
                         f"name")
    name = f"{__name__}.{system}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise FileNotFoundError(f"no reference for system {system!r}: "
                                f"bench/reference/{system}.py is missing"
                                ) from None
