"""The plain reference of the cluster's semantics, written from its
configuration file and nothing of the program.

* :mod:`ref.fleet` — the configuration's fleet as groups of one GPU kind,
  read from either form a configuration states it in;
* :mod:`ref.testbed` — one group's MIG menu and ground-truth speed model;
* :mod:`ref.unet` — the MPS -> MIG predictor as a NumPy float64 forward,
  its linear heads and the memory monitor;
* :mod:`ref.sim` — one replica of the cluster as a per-event loop, each
  GPU on its group's menu, speeds, estimator and speed scale:
  FCFS admission, least-loaded placement, MISO's checkpoint -> MPS probe ->
  estimate -> Algorithm 1 -> reconfigure pipeline, and Algorithm 1 as a
  literal enumeration of every valid partition and assignment.
"""
