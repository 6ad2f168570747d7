"""90th percentile of the window's cycle times, from the harness's own clock at
cycle boundaries. The candidate tail metric (see PERF.md); the count is on stderr."""


def read(run):
    cycles = sorted(run.window.cycle_seconds())
    if len(cycles) < 10:
        return None
    return 1e3 * cycles[min(len(cycles) - 1, int(0.9 * len(cycles)))]
