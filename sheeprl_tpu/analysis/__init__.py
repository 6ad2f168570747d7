"""JAX-aware static analysis + AOT program-contract gate (``sheeprl.py lint``).

Every hazard class this framework has hit shipped first and was caught later by
a one-off fix: ``jax.devices()`` handing a non-rank-0 actor another process's device (PR 10),
the Pallas GRU inheriting an unsupported Mosaic dot precision (PR 10), donation
silently disabled by ``np.asarray`` host views (PR 1), and telemetry events
emitted outside the schema registry (PR 11). This package turns each of those
into a standing, pre-chip check:

- :mod:`~sheeprl_tpu.analysis.engine` walks the package's AST once and runs the
  rule catalog (:mod:`~sheeprl_tpu.analysis.rules`), yielding findings shaped
  like ``obs/diagnose.py``'s: {rule, severity, file, line, summary, suggestion};
- :mod:`~sheeprl_tpu.analysis.programs` is the fused-program registry: the
  donated ``jax.jit`` programs of algos/serve register an AOT builder via
  :func:`register_fused_program`, and :func:`aot_sweep` lowers each for
  ("cpu", "tpu") off-chip and asserts its declared contract (donation survives,
  no host callbacks, expected collectives/custom calls present);
- :mod:`~sheeprl_tpu.analysis.waivers` reads the checked-in
  ``analysis/waivers.toml`` (every entry requires a reason) so the gate starts
  at zero findings and stays there.

See ``howto/static_analysis.md`` for the rule catalog and waiver format.
"""

from sheeprl_tpu.analysis.engine import Finding, lint_main, run_lint
from sheeprl_tpu.analysis.programs import (
    FUSED_PROGRAMS,
    ProgramContract,
    aot_sweep,
    check_program_contract,
    register_fused_program,
)
from sheeprl_tpu.analysis.waivers import load_waivers

__all__ = [
    "Finding",
    "run_lint",
    "lint_main",
    "load_waivers",
    "register_fused_program",
    "FUSED_PROGRAMS",
    "ProgramContract",
    "aot_sweep",
    "check_program_contract",
]
