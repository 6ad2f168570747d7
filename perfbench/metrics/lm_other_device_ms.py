"""Device time an iteration that no other part's metric of the cell reads: the scopes
`embed`, `gae` and `ppo_loss`, and the ops under a phase and no part (the norms and
residual adds between the parts, sampling, the env's step, the trajectory's writes), with
the few under no scope at all (`lm_unscoped_device_share` guards those). With the other
parts' metrics this adds up to the program's leaf-op time, so no part is read by
subtraction."""

from perfbench.harness import lm_spans


def read(run):
    return lm_spans.from_capture(run, lm_spans.part_ms, ("embed", "gae", "ppo_loss", None))
