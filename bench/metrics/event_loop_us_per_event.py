"""Self time of the event loop per simulated event: the benchmark's spans
around ``ClusterSim.run_until_collect`` less the program's own placement,
Algorithm-1 and estimator buckets that grew inside them, over the events
the program counted (``SimConfig(profile=True)``)."""
from probe import EVENT_LOOP, EVENT_LOOP_NESTED


def read(run):
    events = run.prof.get("events")
    if not events:
        return None
    return (run.spans[EVENT_LOOP] - run.spans[EVENT_LOOP_NESTED]) / events * 1e6
