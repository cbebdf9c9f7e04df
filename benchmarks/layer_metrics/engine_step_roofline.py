"""Kernels: the production steps' share of their HBM roofline in the traced
window.  Least time = bytes the dispatches had to move (a function of the
engine's capacity and the dispatch counts, benchmarks/harness/peaks.py, events
and dirty rows counted at their smallest pad) / the chip's peak bytes/s; over
the summed device time of the steps' XLA modules.  Bound by bandwidth: the
step is integer compares and reductions."""
from benchmarks.harness import peaks


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    n_fast = tr["module_count"].get(peaks.FAST_STEP, 0)
    n_refresh = tr["module_count"].get(peaks.REFRESH_STEP, 0)
    t = (tr["module_time_s"].get(peaks.FAST_STEP, 0.0)
         + tr["module_time_s"].get(peaks.REFRESH_STEP, 0.0))
    if not (n_fast + n_refresh) or t <= 0:
        return None
    g = ctx["config"]["engine"]["max_groups"]
    p = ctx["config"]["engine"]["max_peers"]
    moved = (n_fast * peaks.fast_step_bytes(g, p)
             + n_refresh * peaks.refresh_step_bytes(g, p))
    peak = peaks.peaks_for(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (moved / peak) / t
