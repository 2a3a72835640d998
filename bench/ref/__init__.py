"""The plain reference of the testbed's semantics, written from its
configuration file and nothing of the program.

* :mod:`ref.testbed` — the MIG menu and the ground-truth speed model;
* :mod:`ref.unet` — the MPS -> MIG predictor as a NumPy float64 forward,
  its linear heads and the memory monitor;
* :mod:`ref.sim` — one replica of the cluster as a per-event loop:
  FCFS admission, least-loaded placement, MISO's checkpoint -> MPS probe ->
  estimate -> Algorithm 1 -> reconfigure pipeline, and Algorithm 1 as a
  literal enumeration of every valid partition and assignment.
"""
