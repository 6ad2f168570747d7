"""Device time an iteration that no other part's metric of the cell reads: the scopes `embed`, `gae` and
`ppo_loss`, and the ops under a phase and no part (the residual adds and the feed-forward's norm between
the parts, sampling, the env's step, the trajectory's writes), with the few under no scope at all
(`dsv3_unscoped_device_share` guards those). With `dsv3_mla`, `router`, `experts`, `shared_expert`,
`dense_ffn`, `head` and `optimizer` this adds up to the program's leaf-op time."""

from perfbench.harness import dsv3_spans


def read(run):
    return dsv3_spans.from_capture(run, dsv3_spans.part_ms, ("embed", "gae", "ppo_loss", None))
