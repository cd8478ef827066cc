"""Code the harness finds by name, one file each, so that a later cell,
traffic kind or metric comes as a new file and no file here changes:

* `builders/<kind>.py`: a configuration file's `kind`. It provides
  `build(cfg, seed)`, the deployment made from the seed, with `close()`.
* `drivers/<kind>.py`: a traffic file's `kind`. It provides
  `driver(dep, traffic, seed)`, the load generator, with `warm_up()`,
  `window(seconds) -> (records, t0, t_end)`, `attempted_failed(records)`
  and `check(cell, run) -> [Check]`; and `control_readings(cell, seed,
  seconds)`, the readings `control.py` prints.
* `metrics/<name>.py`: a metric's `read(run)`, None where it finds
  nothing to read.
"""
from __future__ import annotations

import importlib.util
import re
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
GROUPS = ("builders", "drivers", "metrics")

_loaded: dict = {}


def load(group: str, name: str):
    """The module `<group>/<name>.py`, loaded once per process."""
    if group not in GROUPS:
        raise ValueError(f"no group {group!r}; known: {GROUPS}")
    key = (group, name)
    if key not in _loaded:
        path = BENCH_DIR / group / f"{name}.py"
        if not path.is_file():
            known = sorted(p.stem for p in (BENCH_DIR / group).glob("*.py"))
            raise LookupError(f"no {group}/{name}.py; known: {known}")
        mod_name = f"chipbench_{group}_" + re.sub(r"\W", "_", name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        _loaded[key] = mod
    return _loaded[key]
