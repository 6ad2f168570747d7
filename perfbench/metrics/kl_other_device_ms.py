"""Device time an iteration that no other part's metric of the cell reads: the scopes `embed`, `gae`,
`ppo_loss` and `optimizer` (Adam over 602.5M parameters, four steps), and the ops under a phase and no part
(the residual adds and norms between the parts, sampling, the env's step, the trajectory's writes), with
the few under no scope at all (`kl_unscoped_device_share` guards those). With `kl_kda`, `kl_mla`, `router`,
`experts`, `shared_expert`, `dense_ffn` and `head` this adds up to the program's leaf-op time."""

from perfbench.harness import kl_spans


def read(run):
    return kl_spans.from_capture(run, kl_spans.part_ms, ("embed", "gae", "ppo_loss", "optimizer", None))
