"""Device time an iteration that no other part's metric of the cell reads: the scopes `embed`, `gae`,
`ppo_loss` and `optimizer` (Adam over 490.3M parameters, eight steps), and the ops under a phase and no
part (the residual adds and norms between the parts, sampling, the env's step, the trajectory's writes),
with the few under no scope at all (`lg_unscoped_device_share` guards those). With `lg_swa`,
`lg_full_attn`, `router`, `experts`, `shared_expert`, `dense_ffn` and `head` this adds up to the
program's leaf-op time."""

from perfbench.harness import lg_spans


def read(run):
    return lg_spans.from_capture(run, lg_spans.part_ms, ("embed", "gae", "ppo_loss", "optimizer", None))
