"""Share of the windows' wall time inside the loop's `env_step` span, `envs.step` (`window.spans.env_step`). The
replay add is what is left of `host_env_act_share` after this and `host_act_share`."""

from perfbench.harness.program_spans import span_share


def read(run):
    return span_share(run, "env_step")
