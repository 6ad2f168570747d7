"""Device time an iteration under the expert layers' `router` scope, rollout and update: scores,
top-k, the sort of the (token, expert) pairs, the dispatch and the combine."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, ("router",))
