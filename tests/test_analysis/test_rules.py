"""Per-rule fixture tests for the graftlint engine: every rule must FIRE on a
synthetic snippet encoding its hazard pattern (positive) and stay SILENT on the
compliant spelling (negative) — the acceptance bar of ISSUE 13. Fixtures are
tiny fake packages written under tmp_path/sheeprl_tpu so the engine walks them
exactly as it walks the real tree."""

from __future__ import annotations

import textwrap

import pytest

from sheeprl_tpu.analysis.engine import Package, run_lint
from sheeprl_tpu.analysis.rules import (
    AsarrayDonationRule,
    CfgKeyResolvesRule,
    HostSyncInJitRule,
    JaxDevicesRule,
    LoopHooksRule,
    PallasDotPrecisionRule,
    TelemetryEventSchemaRule,
)

pytestmark = pytest.mark.lint


def _package(tmp_path, files):
    pkg = tmp_path / "sheeprl_tpu"
    for rel, source in files.items():
        path = pkg / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    return tmp_path


def _findings(tmp_path, rule, files):
    root = _package(tmp_path, files)
    report = run_lint(root=str(root), rules=[rule], use_waivers=False)
    return report["findings"]


# ---- jax-devices-global-view ---------------------------------------------------


def test_jax_devices_fires_outside_fabric(tmp_path):
    found = _findings(
        tmp_path,
        JaxDevicesRule(),
        {"utils/x.py": "import jax\ndevice = jax.devices()[0]\n"},
    )
    assert len(found) == 1
    assert found[0]["rule"] == "jax-devices-global-view"
    assert found[0]["file"] == "sheeprl_tpu/utils/x.py" and found[0]["line"] == 2


def test_jax_devices_allowed_in_fabric_and_local_devices_everywhere(tmp_path):
    found = _findings(
        tmp_path,
        JaxDevicesRule(),
        {
            "parallel/fabric.py": "import jax\nall_devices = jax.devices()\n",
            "utils/x.py": "import jax\ndevice = jax.local_devices()[0]\n",
        },
    )
    assert found == []


# ---- pallas-dot-precision ------------------------------------------------------

_KERNEL_TEMPLATE = """
    import functools
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _kernel(x_ref, w_ref, o_ref):
        o_ref[...] = {dot}

    def run(x, w):
        return pl.pallas_call(
            functools.partial(_kernel),
            out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
        )(x, w)
"""


def test_unpinned_kernel_dot_fires(tmp_path):
    found = _findings(
        tmp_path,
        PallasDotPrecisionRule(),
        {"ops/k.py": _KERNEL_TEMPLATE.format(dot="jnp.dot(x_ref[...], w_ref[...])")},
    )
    assert len(found) == 1 and found[0]["rule"] == "pallas-dot-precision"


def test_bare_matmul_in_kernel_fires(tmp_path):
    found = _findings(
        tmp_path,
        PallasDotPrecisionRule(),
        {"ops/k.py": _KERNEL_TEMPLATE.format(dot="x_ref[...] @ w_ref[...]")},
    )
    assert len(found) == 1 and "`@` matmul" in found[0]["summary"]


def test_pinned_kernel_dot_is_silent_and_dots_outside_kernels_ignored(tmp_path):
    found = _findings(
        tmp_path,
        PallasDotPrecisionRule(),
        {
            "ops/k.py": _KERNEL_TEMPLATE.format(
                dot="jnp.dot(x_ref[...], w_ref[...], precision=jax.lax.Precision.DEFAULT)"
            ),
            # a dot in a pallas-importing module but OUTSIDE any kernel is host/XLA code
            "ops/other.py": (
                "import jax.numpy as jnp\n"
                "from jax.experimental.pallas import pallas_call\n"
                "def host(a, b):\n"
                "    return jnp.dot(a, b)\n"
            ),
        },
    )
    assert found == []


# ---- asarray-into-donated ------------------------------------------------------

_DONATED = """
    from functools import partial
    import jax
    import numpy as np

    @partial(jax.jit, donate_argnums=(0, 1))
    def train(params, opt_state, data, key):
        return params, opt_state

    def loop(params, opt_state, data, key):
        {call}
        return params
"""


def test_asarray_at_donated_position_fires(tmp_path):
    found = _findings(
        tmp_path,
        AsarrayDonationRule(),
        {"algos/a.py": _DONATED.format(call="params, opt_state = train(np.asarray(params), opt_state, data, key)")},
    )
    assert len(found) == 1 and "donated argument 0" in found[0]["summary"]


def test_asarray_through_local_variable_fires(tmp_path):
    call = "snap = np.asarray(opt_state)\n        params, _ = train(params, snap, data, key)"
    found = _findings(tmp_path, AsarrayDonationRule(), {"algos/a.py": _DONATED.format(call=call)})
    assert len(found) == 1 and "donated argument 1" in found[0]["summary"]


def test_asarray_at_undonated_position_is_silent(tmp_path):
    found = _findings(
        tmp_path,
        AsarrayDonationRule(),
        {"algos/a.py": _DONATED.format(call="params, opt_state = train(params, opt_state, data, np.asarray(key))")},
    )
    assert found == []


# ---- host-sync-in-jit ----------------------------------------------------------

_JITTED = """
    from functools import partial
    import time
    import jax
    import numpy as np

    def helper(x):
        {body}

    @partial(jax.jit, donate_argnums=(0,))
    def program(x):
        return helper(x)
"""


@pytest.mark.parametrize(
    "body, marker",
    [
        ("return x.item()", ".item()"),
        ("return np.asarray(x)", "np.asarray"),
        ("t = time.time(); return x * t", "time.time"),
        ("print(x); return x", "print()"),
    ],
)
def test_host_sync_reachable_from_jit_fires(tmp_path, body, marker):
    found = _findings(tmp_path, HostSyncInJitRule(), {"algos/a.py": _JITTED.format(body=body)})
    assert len(found) == 1 and marker in found[0]["summary"]


def test_host_sync_in_unreachable_helper_is_silent(tmp_path):
    source = """
        import jax
        import numpy as np

        def host_only(x):
            return np.asarray(x)

        @jax.jit
        def program(x):
            return x * 2
    """
    found = _findings(tmp_path, HostSyncInJitRule(), {"algos/a.py": source})
    assert found == []


def test_jit_of_foreign_method_does_not_claim_local_def(tmp_path):
    # jax.jit(self._env.reset) wraps ANOTHER object's method — the local host
    # wrapper that happens to share the name must not become a jit root
    source = """
        import jax
        import numpy as np

        class Host:
            def __init__(self, env):
                self._reset_fn = jax.jit(env.reset)

            def reset(self):
                return np.asarray(self._reset_fn())
    """
    found = _findings(tmp_path, HostSyncInJitRule(), {"envs/e.py": source})
    assert found == []


# ---- telemetry-event-unregistered ----------------------------------------------


def test_unregistered_event_fires_and_registered_is_silent(tmp_path):
    rule = TelemetryEventSchemaRule(registered_names={"window", "summary"})
    found = _findings(
        tmp_path,
        rule,
        {
            "obs/t.py": (
                "def produce(emit):\n"
                '    emit("window", step=1)\n'
                '    emit("mystery_event", step=2)\n'
            )
        },
    )
    assert len(found) == 1 and "mystery_event" in found[0]["summary"]


def test_event_names_parsed_from_schema_module(tmp_path):
    # no override: the rule reads _STRICT_EVENTS/_OPEN_EVENTS from the fixture's
    # own obs/schema.py, exactly as it does on the real tree
    found = _findings(
        tmp_path,
        TelemetryEventSchemaRule(),
        {
            "obs/schema.py": (
                "_STRICT_EVENTS = {\"start\": {}}\n"
                "_OPEN_EVENTS = {\"health\": {}}\n"
            ),
            "obs/t.py": (
                "def produce(emit):\n"
                '    emit("start")\n'
                '    emit("health")\n'
                '    emit("rogue")\n'
            ),
        },
    )
    assert len(found) == 1 and "rogue" in found[0]["summary"]


# ---- loop-hooks-incomplete -----------------------------------------------------

_HOOKED_LOOP = """
    from sheeprl_tpu.utils.registry import register_algorithm
    from sheeprl_tpu.obs import build_telemetry
    from sheeprl_tpu.resilience import build_resilience

    @register_algorithm()
    def main(fabric, cfg):
        telemetry = build_telemetry(fabric, cfg, ".")
        resilience = build_resilience(fabric, cfg, ".")
        for step in range(10):
            telemetry.observe_train(1, None)
            telemetry.observe_learn(None)
            telemetry.step(step)
            resilience.step(step)
            if resilience.preempt_requested():
                break
        resilience.finalize(10)
        telemetry.close(10)
"""

_BARE_LOOP = """
    from sheeprl_tpu.utils.registry import register_algorithm

    @register_algorithm()
    def main(fabric, cfg):
        for step in range(10):
            pass
"""


def test_hookless_entrypoint_fires(tmp_path):
    found = _findings(tmp_path, LoopHooksRule(), {"algos/bare/bare.py": _BARE_LOOP})
    assert len(found) == 1
    assert "build_telemetry" in found[0]["summary"] and "resilience.finalize" in found[0]["summary"]


def test_fully_hooked_entrypoint_is_silent(tmp_path):
    found = _findings(tmp_path, LoopHooksRule(), {"algos/good/good.py": _HOOKED_LOOP})
    assert found == []


def test_hooks_found_through_cross_module_delegation(tmp_path):
    # the p2e-finetuning shape: a registered main that delegates to another
    # module's hooked loop (module-alias attribute call)
    found = _findings(
        tmp_path,
        LoopHooksRule(),
        {
            "algos/good/good.py": _HOOKED_LOOP.replace("@register_algorithm()\n    ", ""),
            "algos/fine/fine.py": """
                from sheeprl_tpu.algos.good import good
                from sheeprl_tpu.utils.registry import register_algorithm

                @register_algorithm()
                def main(fabric, cfg):
                    return good.main(fabric, cfg)
            """,
        },
    )
    assert found == []


# ---- cfg-key-unresolved --------------------------------------------------------

_UNION = {"algo": {"gamma": 0.99, "name": "x"}, "env": {"id": "y"}}


def test_unknown_group_key_fires(tmp_path):
    found = _findings(
        tmp_path,
        CfgKeyResolvesRule(union_tree=_UNION),
        {"algos/a.py": "def f(cfg):\n    return cfg.algo.gmama\n"},
    )
    assert len(found) == 1 and "cfg.algo.gmama" in found[0]["summary"]


def test_known_keys_stores_and_unknown_roots_are_silent(tmp_path):
    found = _findings(
        tmp_path,
        CfgKeyResolvesRule(union_tree=_UNION),
        {
            "algos/a.py": (
                "def f(cfg):\n"
                "    g = cfg.algo.gamma\n"
                "    cfg.algo.dynamic_key = 1\n"       # store defines it...
                "    h = cfg.algo.dynamic_key\n"       # ...so the load is fine
                "    i = cfg.checkpoint_path\n"        # unknown top-level root: runtime-built
                "    j = cfg.env.get('id')\n"          # dict-method access
                "    return g, h, i, j\n"
            )
        },
    )
    assert found == []


# ---- engine mechanics ----------------------------------------------------------


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    root = _package(tmp_path, {"broken.py": "def f(:\n"})
    report = run_lint(root=str(root), rules=[], use_waivers=False)
    assert [f["rule"] for f in report["findings"]] == ["parse-error"]


def test_package_walk_indexes_by_rel_path(tmp_path):
    root = _package(tmp_path, {"a.py": "x = 1\n", "sub/b.py": "y = 2\n"})
    package = Package(root)
    assert package.module("sheeprl_tpu/sub/b.py") is not None
    assert {m.rel for m in package.modules} == {"sheeprl_tpu/a.py", "sheeprl_tpu/sub/b.py"}
