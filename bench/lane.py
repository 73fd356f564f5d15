"""Model lanes: everything model-specific, found by the config file's name.

Each config file says ``"lane": "<name>"``, and ``lanes/<name>.py`` is
the module the harness then takes every part that depends on the block
shape from.  A lane module provides:

* ``dims(c)``: the sizes its other functions read, from the config file;
* ``layout(c)``: the parameter tree, leaf -> ``(shape, init)``, that
  ``weights.make`` fills (the program's checkpoint layout);
* ``logits(c, w, tokens, precision)``: the plain float32 reference
  forward of one sequence, or the fp8 control with ``precision="fp8"``,
  built on the helpers of ``reference.py``;
* ``fields(c)`` and ``check(cfg, c)``: the config-file -> ``ModelConfig``
  fields beyond ``program.FIELDS``, and the guard that raises
  ``ValueError`` where the program's model departs from what the
  lane's reference implements;
* ``per_position(c)``: model FLOPs of one position (``flops.span``);
* ``weight_bytes(c)``, ``kv_bytes_per_position(c)`` and
  ``round_bytes(c, counters)``: the least HBM bytes (``hbm.py``), the
  last from the counters of one ``sched.round`` span.

``reference``, ``weights``, ``flops``, ``hbm`` and ``program`` keep their
own functions of these names as dispatchers, so a new architecture
comes in as a lane file and a config file that names it.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

LANES = Path(__file__).resolve().with_name("lanes")


def names() -> list:
    """The lanes found in ``LANES``."""
    return sorted(p.stem for p in LANES.glob("*.py"))


def load(c: dict):
    """The lane module that config ``c`` names; a config without a
    ``lane``, or one that names no file, is an error, never a default."""
    if "lane" not in c:
        raise KeyError(f"config {c.get('name')!r} names no lane; add "
                       f"\"lane\": one of {names()}")
    path = LANES / f"{c['lane']}.py"
    if not path.is_file():
        raise ValueError(f"config {c.get('name')!r} names lane "
                         f"{c['lane']!r}, which is not one of "
                         f"{names()} in {LANES}")
    return _module(str(path))


@functools.cache
def _module(path: str):
    spec = importlib.util.spec_from_file_location(
        "lane_" + Path(path).stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
