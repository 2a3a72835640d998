"""What the benchmark counts, times and records inside the program, from
outside it.

A :class:`Probe` is installed once per process, before the window, and
wraps these program calls:

* ``UNet.__call__``, the estimator's one device program: calls and the
  batch sizes sent (a copy of the dispatch counter of ``chip_smoke.py``'s
  ``Probe``);
* ``UNetEstimator._postprocess``, once per MPS window with that window's
  row of the U-Net's output, and ``BatchSim._fuse_estimates`` (stage A),
  whose collected windows say which replica, GPU and jobs the row belongs
  to and which matrix went in;
* ``Policy._apply_choice``, through which every Algorithm-1 decision is
  applied: the replica, GPU, jobs and partition;
* JAX's monitoring events: a backend-compile event fires for every program
  JAX compiles or loads from the persistent cache, so the count inside the
  window must stay 0;
* with ``spans=True`` (the traced run only), the layer calls of a replay:
  ``ClusterSim.run_until_collect`` (event loop and placement),
  ``BatchSim._fuse_estimates`` (stage A, the estimator) and
  ``BatchSim._solve_decisions`` (stage C, Algorithm 1), each timed on the
  host clock and written into the profiler trace as a
  ``jax.profiler.TraceAnnotation`` of the same name.

Windows and decisions are kept by replica, as ``(replay, index)``; the
check reads them after the window.
"""
from __future__ import annotations

import collections
import functools
import time

import numpy as np

#: span names, as they appear in the profiler trace and in ``Probe.spans``
EVENT_LOOP, ESTIMATOR, ALG1 = "event_loop", "estimator", "alg1"
STEP, BUILD = "step", "build"
#: seconds of the program's own profile buckets (placement, Algorithm 1,
#: estimator) that accrued inside ``EVENT_LOOP`` spans
EVENT_LOOP_NESTED = "event_loop_nested"
_NESTED = ("placement_s", "alg1_s", "estimator_s")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Probe:
    def __init__(self, spans: bool = False):
        import jax

        from repro.core.estimators import UNetEstimator
        from repro.core.predictor import unet
        from repro.core.sim.batch import BatchSim
        from repro.core.sim.engine import ClusterSim
        from repro.core.sim.policies.base import Policy

        self.compiles = 0
        self.calls = 0
        self.batches = collections.Counter()   # batch size -> calls
        self.owner = {}                # id(sim) -> (replay, replica index)
        self.works = {}                # id(profs) -> stage A's window
        #: (replay, index) -> [(gid, jids, matrix in, U-Net row out)]
        self.windows = collections.defaultdict(list)
        #: (replay, index) -> [(gid, jids, partition)]
        self.decisions = collections.defaultdict(list)
        self.strays = 0                # U-Net rows no window claimed
        self.spans = collections.defaultdict(float)   # name -> seconds
        self.annotate = jax.profiler.TraceAnnotation
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)

        call, post = unet.UNet.__call__, UNetEstimator._postprocess
        fuse, apply_choice = (BatchSim.__dict__["_fuse_estimates"],
                              Policy._apply_choice)
        self._restore = [
            (unet.UNet, "__call__", call),
            (UNetEstimator, "_postprocess", post),
            (BatchSim, "_fuse_estimates", fuse),
            (BatchSim, "_solve_decisions",
             BatchSim.__dict__["_solve_decisions"]),
            (ClusterSim, "run_until_collect", ClusterSim.run_until_collect),
            (Policy, "_apply_choice", apply_choice)]
        probe = self

        @functools.wraps(call)
        def counted(net, mps_matrix):
            probe.calls += 1
            probe.batches[1 if mps_matrix.ndim == 2 else len(mps_matrix)] += 1
            return call(net, mps_matrix)

        @functools.wraps(post)
        def row(est, profs, pred, qos=None):
            w = probe.works.pop(id(profs), None)
            if w is None or w.profs is not profs:
                probe.strays += 1
            else:
                key = probe.owner.get(id(w.g.sim))
                if key is not None:
                    probe.windows[key].append(
                        (w.g.gid, tuple(w.jids), w.mat, np.array(pred)))
            return post(est, profs, pred, qos)

        def stage_a(works):
            probe.works = {id(w.profs): w for w in works}
            try:
                return fuse.__func__(works)
            finally:
                probe.works = {}

        @functools.wraps(apply_choice)
        def applied(policy, g, jids, choice, overhead):
            key = probe.owner.get(id(policy.sim))
            if key is not None:
                probe.decisions[key].append(
                    (g.gid, tuple(jids), tuple(choice.partition)))
            return apply_choice(policy, g, jids, choice, overhead)

        unet.UNet.__call__ = counted
        UNetEstimator._postprocess = row
        BatchSim._fuse_estimates = staticmethod(stage_a)
        Policy._apply_choice = applied
        if spans:
            ClusterSim.run_until_collect = self._loop_span(
                ClusterSim.run_until_collect)
            BatchSim._fuse_estimates = staticmethod(self.spanned(
                ESTIMATOR, stage_a))
            BatchSim._solve_decisions = staticmethod(self.spanned(
                ALG1, BatchSim._solve_decisions))

    def own(self, replay: int, sims) -> None:
        """Records of ``sims`` from here on belong to ``replay``."""
        self.owner = {id(s): (replay, i) for i, s in enumerate(sims)}

    def close(self) -> None:
        """Put the wrapped program calls back."""
        for owner, name, fn in self._restore:
            setattr(owner, name, fn)

    def _on_dur(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self.compiles += 1

    def _loop_span(self, fn):
        """The ``EVENT_LOOP`` span, which also books the program's profile
        buckets that grew inside it (``SimConfig(profile=True)``)."""
        timed, spans = self.spanned(EVENT_LOOP, fn), self.spans

        @functools.wraps(fn)
        def loop(sim):
            p = sim.prof
            before = sum(p[k] for k in _NESTED)
            out = timed(sim)
            spans[EVENT_LOOP_NESTED] += sum(p[k] for k in _NESTED) - before
            return out
        return loop

    def spanned(self, name, fn):
        """``fn`` timed into ``spans[name]`` and annotated in the trace."""
        spans, annotate = self.spans, self.annotate

        @functools.wraps(fn)
        def timed(*args, **kw):
            t = time.perf_counter()
            with annotate(name):
                out = fn(*args, **kw)
            spans[name] += time.perf_counter() - t
            return out
        return timed
