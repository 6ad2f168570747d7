"""Device time an iteration that no other part's metric of the cell reads: the scopes `embed`,
`gae` and `ppo_loss`, and the ops under a phase and no part (the norms and residual adds between the
parts, sampling, the env's step, the trajectory's writes), with the few under no scope at all
(`q3n_unscoped_device_share` guards those). With `q3n_linear_attn`, `attention`, `router`, `experts`,
`shared_expert`, `head` and `optimizer` this adds up to the program's leaf-op time."""

from perfbench.harness import q3n_spans


def read(run):
    return q3n_spans.from_capture(run, q3n_spans.part_ms, ("embed", "gae", "ppo_loss", None))
