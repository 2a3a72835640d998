"""The configuration's fleet as groups of GPUs of one kind.

A configuration states its fleet in one of two forms:

* ``fleet``: a list of groups in GPU-id order, each with ``kind``,
  ``gpus``, ``speed_scale``, ``mig`` (the MIG menu), ``hardware`` (the
  speed model's constants) and ``predictor`` (the U-Net's weights and
  shape);
* or, for a fleet of one kind, ``kind``, ``gpus``, ``mig``, ``hardware``
  and ``predictor`` at the top level: one group, of speed scale 1.0.

Work is counted in seconds of the reference GPU: a GPU of speed scale
``s`` progresses ``s`` times faster than its own normalized speeds say,
in every phase.  :func:`groups` reads both forms into the first; nothing
downstream reads the configuration's fleet otherwise.
"""
from __future__ import annotations

from typing import List

#: what every group states
KEYS = ("kind", "gpus", "speed_scale", "mig", "hardware", "predictor")
#: the keys a fleet of one kind states at the top level
TOP = ("kind", "gpus", "mig", "hardware", "predictor")


def groups(config: dict) -> List[dict]:
    """The configuration's groups in GPU-id order, each with :data:`KEYS`
    alone.  Raises ``ValueError`` on a fleet stated in both forms, or a
    group that lacks a key or holds no GPU."""
    if "fleet" in config:
        both = [k for k in TOP if k in config]
        if both:
            raise ValueError(f"the configuration states 'fleet' and "
                             f"{both[0]!r} at its top level")
        stated = config["fleet"]
    else:
        stated = [dict({k: config[k] for k in TOP}, speed_scale=1.0)]
    if not stated:
        raise ValueError("'fleet' holds no group")
    out = []
    for i, g in enumerate(stated):
        missing = [k for k in KEYS if k not in g]
        if missing:
            raise ValueError(f"fleet group {i} has no {missing[0]!r}")
        if int(g["gpus"]) < 1:
            raise ValueError(f"fleet group {i}: 'gpus' is {g['gpus']!r}")
        out.append({k: g[k] for k in KEYS})
    return out


def gpu_groups(fleet: List[dict]) -> List[int]:
    """The group of each GPU, by GPU id."""
    return [i for i, g in enumerate(fleet) for _ in range(int(g["gpus"]))]
