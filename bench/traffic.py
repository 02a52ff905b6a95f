"""Traffic generation: tenants and their training-token streams.

Everything here is read from a traffic file (``bench/traffic/<name>.json``)
and the run's ``--seed``; nothing comes from the program under test.  The
dataset length profiles and the token-stream recurrence are copies of the
program's synthetic corpus (``repro.data.synthetic``), kept here so that a
later change to the program cannot move the yardstick.  The program makes
its training tokens from the same recurrence and seed (its service takes no
data feed); the reference regenerates them with the copy below.

Every seed gives the same work on other tokens.  Training-sequence
lengths are a frozen sample stored in the traffic file, so every seed gives
every tenant the same lengths and the compiled shapes stay the same.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

import blocks

#: pad length, mean and std of the true length as fractions of the pad
#: (``repro.data.synthetic.DATASETS``: SST-2, OpenBookQA, RTE)
NORMAL_PROFILES = {
    "sst2": (64, 0.55, 0.25),
    "qa": (128, 0.60, 0.22),
    "rte": (256, 0.50, 0.25),
}


def frozen_lengths(profile: Dict, n: int, seed: int) -> List[int]:
    """``n`` true sequence lengths of one length profile (the sample that a
    traffic file freezes).  ``normal`` profiles follow the program's
    synthetic corpus; ``lognormal`` ones are set by the traffic file."""
    rng = np.random.RandomState(seed)
    if profile["dist"] == "normal":
        pad, mean, std = NORMAL_PROFILES[profile["name"]]
        raw = rng.normal(mean, std, n) * pad
        lens = np.clip(np.round(raw), 8, pad)
    else:
        raw = rng.lognormal(np.log(profile["median"]), profile["sigma"], n)
        lens = np.clip(np.round(raw), profile["min"], profile["max"])
    return [int(x) for x in lens]


def token_stream(task_id: str, vocab: int, seed: int) -> Iterator[int]:
    """The per-tenant training-token recurrence (a copy of the program's
    ``repro.data.synthetic.token_stream``): an affine recurrence with 10%
    noise tokens, seeded from a CRC of ``task_id:seed``."""
    h = zlib.crc32(f"{task_id}:{seed}".encode()) % (2**31)
    rng = np.random.RandomState(h)
    v = max(vocab - 2, 2)
    a = 3 + 2 * (h % 11)
    c = 1 + (h % 97)
    x = rng.randint(1, v)
    while True:
        if rng.rand() < 0.1:
            x = int(rng.randint(1, v))
        else:
            x = int((a * x + c) % v) or 1
        yield x


@dataclass(frozen=True)
class Tenant:
    """One tenant of a traffic file."""

    tenant_id: str
    method: str        # lora | adapter | prefix
    rank: int          # LoRA rank, bottleneck width or prefix length
    targets: tuple     # matmuls the method attaches to
    lengths: tuple     # the frozen micro-batch sequence lengths
    pad_len: int
    lr: float
    alpha: float

    @property
    def micro_batch(self) -> int:
        return len(self.lengths)

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


def tenants(traffic: Dict, cfg: Dict) -> List[Tenant]:
    """The traffic file's tenant table, resolved against a configuration:
    a tenant's ``"targets"`` group (``"attn"`` where it names none) is the
    sites the configuration's block family gives that group."""
    fam = blocks.family(cfg)
    out = []
    for t in traffic["tenants"]:
        targets = fam.targets(cfg, t.get("targets", "attn"))
        prof = traffic["length_profiles"][t["lengths"]]
        out.append(Tenant(
            tenant_id=t["id"], method=t["method"], rank=int(t["rank"]),
            targets=tuple(targets), lengths=tuple(t["frozen_lengths"]),
            pad_len=int(prof["pad"]), lr=float(traffic["lr"]),
            alpha=float(traffic["alpha"])))
    return out
