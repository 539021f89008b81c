"""Client driver: 99th percentile latency of the ops issued before the
profiler starts in a traced run, each timed from its due time to the
return of the call that carried it.  Ops issued later are left out: the
profiler slows the host while it records, and stopping it stalls the
driver.  This tail is no bounded metric: host stalls of 0.1 s and more
land in some runs and not in others (PERF.md, section 2)."""
import numpy as np


def read(run):
    if run.trace_bounds is None:
        return None
    before = run.issue < run.trace_bounds[0]
    lat = run.latency_s[before]
    return None if np.isnan(lat).any() or not len(lat) else \
        1e3 * float(np.percentile(lat, 99))
