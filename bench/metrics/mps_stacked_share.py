"""Share of stage A's MPS memo misses that the stacked fixed-point pass
solved (the rest were solved one by one): ``prof["mps_rows_stacked"]``
over ``prof["mps_rows"] - prof["mps_rows_hit"]``, in percent.  A program
without these counters, or a window with no miss, reads nothing."""


def read(run):
    p = run.prof
    if "mps_rows_stacked" not in p or "mps_rows_hit" not in p:
        return None
    misses = p.get("mps_rows", 0.0) - p["mps_rows_hit"]
    if misses <= 0:
        return None
    return p["mps_rows_stacked"] / misses * 100.0
