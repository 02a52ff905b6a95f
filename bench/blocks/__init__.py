"""Block families, found by name: ``block.family`` of a configuration file
selects the module ``bench/blocks/<family>.py``, which holds everything of
the benchmark that depends on the model's block.  A configuration of a new
family enters the benchmark by new files alone: its configuration, its
family module, its traffic, limits and metric files.

A family module provides:

* ``program_config(cfg)``: the program's ``ArchConfig`` for the
  configuration, checked against the file's sizes (raises otherwise);
* ``dims(cfg)``, ``site_dims(cfg)`` (``(d_in, d_out)`` of every matmul an
  adapter can attach to) and ``targets(cfg, group)``, the sites a traffic
  file's ``"targets"`` group names;
* ``backbone_shapes(cfg)``, ``backbone(cfg, seed)`` (the frozen backbone's
  leaves from the seed, traced inside one jitted call) and
  ``program_backbone(cfg, bb)``, the backbone in the program's tree;
* ``adapter_shapes(cfg, tenant, prefix_rows)``: a tenant's leaves per site,
  ``[L, ...]`` with the site's layers first; ``from_program(cfg, tree)``
  and ``to_program(cfg, stacks)``, the maps between the program's adapter
  tree and the benchmark's per-site stacks ``{kind: {site: {leaf: [L,
  cap, ...]}}}``;
* ``stack(ref, bb, x, ad, pos, kmask, method, scale)``: the reference's
  logits of the block stack, built from the generic parts of
  ``reference.Reference`` (``ref``);
* ``matmul_params(cfg)`` and ``train_flops(cfg, tenants)``, by
  ``bench/work.py``'s conventions.

No family is assumed: a configuration that names none, or a family whose
module does not exist, is refused.
"""
from __future__ import annotations

import functools
import importlib.util
import zlib
from pathlib import Path
from types import ModuleType
from typing import Dict

HERE = Path(__file__).resolve().parent

#: key under which ``harness.find_cell`` records, in a loaded configuration,
#: the blocks directory of the benchmark it came from (a test's benchmark
#: root adds its own families to this directory's)
DIR_KEY = "_blocks_dir"


@functools.lru_cache(maxsize=None)
def _load(path: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"bench_block_{Path(path).stem}_{zlib.crc32(path.encode())}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cfg: Dict) -> ModuleType:
    """The family module a configuration names under ``block.family``:
    ``<name>.py`` in the blocks directory of the benchmark the configuration
    was found in (``DIR_KEY``), else in this one."""
    name = cfg.get("block", {}).get("family")
    if not name:
        raise KeyError("the configuration names no block family (block.family)")
    dirs = [Path(cfg[DIR_KEY])] if DIR_KEY in cfg else []
    paths = list(dict.fromkeys(d / f"{name}.py" for d in dirs + [HERE]))
    for path in paths:
        if path.is_file():
            return _load(str(path.resolve()))
    raise FileNotFoundError(f"block family {name!r}: no module "
                            + " or ".join(str(p) for p in paths))
