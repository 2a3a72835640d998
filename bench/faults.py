"""The control of the check that decides ``correct``, and the faults it
must catch, each planted in the program from outside it.

* ``control``: the program's own lower-precision path, switched on: the
  U-Net's convolutions at ``Precision.HIGH`` (three bfloat16 passes) where
  the configuration states float32;
* ``unet``: the estimator's answer altered where it is produced (every
  U-Net output scaled by 0.999);
* ``alg1``: Algorithm 1's answer altered where it is produced (each
  decision's slices handed to the jobs in reverse order);
* ``completion``: a completion recorded late (each finish time 1e-7 of
  the clock later);
* ``placement``: the placer's answer altered (the candidate with most
  jobs instead of fewest);
* ``half``: half of the batch left out (a replay runs only its first
  half of the replicas);
* ``unchanged``: a step that returns the state unchanged.

The cells run on one chip, so no exchange between chips can be left out.

    python3 bench/faults.py <plant> --workload <cell> --seed <n> --seconds <s>

runs the harness with the plant in place.  Its runs must come out not
correct; the tests under ``tests/bench/`` plant the same faults at a size
the CPU holds.
"""
from __future__ import annotations

import dataclasses
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def plant(name: str, setattr=setattr) -> None:
    """Put fault ``name`` in place (``setattr`` may be a test's
    monkeypatch, which takes it out again)."""
    from repro.core.sim.batch import BatchSim
    from repro.core.sim.engine import ClusterSim
    from repro.core.sim.policies.base import Policy

    if name == "control":
        from jax import lax

        from repro.core.predictor import unet
        setattr(unet, "PRECISION", lax.Precision.HIGH)
    elif name == "unet":
        from repro.core.predictor import unet
        real = unet._apply_jit
        setattr(unet, "_apply_jit", lambda p, m, levels, jobs:
                real(p, m, levels, jobs) * 0.999)
    elif name == "alg1":
        solve = BatchSim._solve_decisions

        def reversed_slices(decisions):
            solve(decisions)
            for d in decisions:
                if d.choice is not None:
                    d.choice = dataclasses.replace(
                        d.choice, partition=tuple(reversed(d.choice.partition)))
        setattr(BatchSim, "_solve_decisions", staticmethod(reversed_slices))
    elif name == "completion":
        finish = ClusterSim._finish

        def late(sim, g, job):
            finish(sim, g, job)
            job.finish_time = sim.t * (1.0 + 1e-7)
        setattr(ClusterSim, "_finish", late)
    elif name == "placement":
        pick = Policy.pick_gpu

        def most_loaded(policy, job):
            if pick(policy, job) is None:
                return None
            return max(policy.placement_candidates(job),
                       key=lambda g: (len(g.jobs), -g.gid))
        setattr(Policy, "pick_gpu", most_loaded)
    elif name == "half":
        init = BatchSim.__init__
        setattr(BatchSim, "__init__",
                lambda self, sims: init(self, list(sims)[:len(sims) // 2]))
    elif name == "unchanged":
        setattr(BatchSim, "step", lambda self: False)
    else:
        raise ValueError(f"unknown plant {name!r}")


NAMES = ("control", "unet", "alg1", "completion", "placement", "half",
         "unchanged")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in NAMES:
        sys.exit(f"usage: faults.py {{{','.join(NAMES)}}} <run.py arguments>")
    sys.path.insert(0, BENCH)
    import run

    run.prepare()
    plant(argv[0])
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
