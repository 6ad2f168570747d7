"""The comparison that decides `correct`: the program's first three gradient steps,
as the timed run itself made them, against the plain reference following the same
steps from the same seed, batches and keys.

Numbers compared (each has a limit in the configuration's file):

for each of the three optimizers' groups (`wm`, `actor`, `critic`):

- `<g>_loss1_gap`, `<g>_loss_gap`: the relative gap of the first step's loss, and the
  widest over the train calls that make up the first three steps;
- `<g>_grad_gap`, `<g>_grad_mid_gap`: the first gradient as the optimizer gets it
  (Adam's first moment after one step: the clipped gradient times `1 - b1` on both
  sides), by the worst leaf and by the median leaf: the gap between the two sides'
  norms of a leaf, against the reference's norm of that leaf or of the group's median
  leaf, whichever is larger;
- `<g>_update_gap`, `<g>_update_mid_gap`: the same two measures of the parameters'
  change after the three steps, leaving out the leaves whose reference gradient is
  under a thousandth of the group's median leaf's (they move by round-off alone under
  Adam);

and
- `act_view_gap`: the widest absolute difference between the act view the loop last
  handed to the player and the trainer's own parameters (a copy: limit 0);
- `act_state_gap`, `act_sample_mismatch`: `PlayerDV3.get_actions` on the host-placed
  act view, the first call after the three steps: the relative gap of the new
  recurrent state, and the share of the categorical draws (posterior and action, made
  from the same key) that differ from the reference's.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List

import numpy as np

GROUPS = ("world_model", "actor", "critic")
LOSSES = {
    "world_model": "Loss/world_model_loss",
    "actor": "Loss/policy_loss",
    "critic": "Loss/value_loss",
}


def leaf_norms(tree) -> Dict[str, float]:
    import jax

    return {
        jax.tree_util.keystr(path): float(np.linalg.norm(np.asarray(leaf).ravel()))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def diff_norms(after, before) -> Dict[str, float]:
    import jax

    return leaf_norms(jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b), after, before))


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float], keep=None):
    """(worst gap, its leaf, the median leaf's gap) between the two sides' norms."""
    if set(program) != set(reference):
        raise ValueError(f"the two sides hold different leaves: {set(program) ^ set(reference)}")
    median = float(np.median(list(reference.values())))
    gaps = {
        leaf: abs(program[leaf] - ref) / max(ref, median, 1e-30)
        for leaf, ref in reference.items()
        if keep is None or leaf in keep
    }
    where = max(gaps, key=gaps.get)
    return gaps[where], where, float(np.median(list(gaps.values())))


def run_reference(m: dict, seed: int, calls: List[dict], recorded_act: dict = None) -> dict:
    """Follow the recorded train calls with the plain reference, then the recorded act
    step with the reference's parameters after them. Returns the initial and final
    parameters, Adam's first moments after the first step, each call's losses and the
    act step's results, all on the host."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import dreamer_v3 as ref

    with jax.default_matmul_precision("highest"):
        params = jax.jit(partial(ref.init_params, m))(np.int32(seed))
        initial = jax.device_get(params)
        opt = ref.init_opt(params)
        moments = {"low": jnp.zeros(()), "high": jnp.zeros(())}
        step = jax.jit(partial(ref.train_step, m), donate_argnums=(0, 1))
        losses, first_mu = [], None
        for call in calls:
            keys = jax.random.split(jnp.asarray(call["key"]), call["steps"])
            per_step = []
            for g in range(call["steps"]):
                batch = {k: jnp.asarray(v[g]) for k, v in call["batch"].items()}
                params, opt, moments, loss = step(
                    params, opt, moments, batch, jnp.asarray(call["cum"] + g), keys[g]
                )
                per_step.append(jax.device_get(loss))
                if first_mu is None:
                    first_mu = jax.device_get({g_: opt[g_]["mu"] for g_ in GROUPS})
            losses.append({k: float(np.mean([s[k] for s in per_step])) for k in GROUPS})
        final = jax.device_get(params)
        act = None
        if recorded_act is not None:
            r = recorded_act
            act = jax.device_get(
                jax.jit(partial(ref.act_step, m))(
                    params, {k: jnp.asarray(v) for k, v in r["obs"].items()}, jnp.asarray(r["a"]),
                    jnp.asarray(r["h"]), jnp.asarray(r["z"]), jnp.asarray(r["key"]),
                )
            )
    return {"initial": initial, "final": final, "first_mu": first_mu, "losses": losses, "act": act}


def numbers(m: dict, recorded: dict, reference: dict) -> Dict[str, dict]:
    """The compared numbers, `{name: {"value": ..., "where": ...}}`."""
    out: Dict[str, dict] = {}
    for group, short in (("world_model", "wm"), ("actor", "actor"), ("critic", "critic")):
        gaps = [
            abs(call["losses"][group] - ref[group]) / max(abs(ref[group]), 1e-30)
            for call, ref in zip(recorded["calls"], reference["losses"])
        ]
        out[f"{short}_loss1_gap"] = {"value": gaps[0], "where": "the first step"}
        out[f"{short}_loss_gap"] = {"value": max(gaps), "where": f"call {int(np.argmax(gaps)) + 1}"}
        ref_grad = leaf_norms(reference["first_mu"][group])
        worst, leaf, middle = leaf_gaps(leaf_norms(recorded["first_mu"][group]), ref_grad)
        out[f"{short}_grad_gap"] = {"value": worst, "where": leaf}
        out[f"{short}_grad_mid_gap"] = {"value": middle, "where": "the median leaf"}
        median = float(np.median(list(ref_grad.values())))
        moved = {leaf for leaf, norm in ref_grad.items() if norm >= 1e-3 * median}
        worst, leaf, middle = leaf_gaps(
            diff_norms(recorded["params_after"][group], reference["initial"][group]),
            diff_norms(reference["final"][group], reference["initial"][group]),
            keep=moved,
        )
        out[f"{short}_update_gap"] = {"value": worst, "where": leaf}
        out[f"{short}_update_mid_gap"] = {"value": middle, "where": "the median leaf"}
    out["act_view_gap"] = {"value": float(recorded["act_view_gap"]), "where": "act view"}
    act, ref_act = recorded.get("act"), reference.get("act")
    if act is not None and ref_act is not None:
        h_ref, z_ref, a_ref = (np.asarray(x, np.float64) for x in ref_act)
        gap = np.linalg.norm(np.asarray(act["h_after"], np.float64) - h_ref) / max(np.linalg.norm(h_ref), 1e-30)
        out["act_state_gap"] = {"value": float(gap), "where": "recurrent state after one act step"}
        classes = m["discrete_size"]
        drawn = lambda x: np.asarray(x, np.float64).reshape(x.shape[0], -1, classes).argmax(-1)  # noqa: E731
        differ = int((drawn(act["z_after"]) != drawn(z_ref)).sum())
        differ += int((np.asarray(act["actions"]).argmax(-1) != a_ref.argmax(-1)).sum())
        total = drawn(z_ref).size + a_ref.shape[0]
        out["act_sample_mismatch"] = {"value": differ / total, "where": f"{differ} of {total} draws"}
    return out


def judge(values: Dict[str, dict], limits: Dict[str, float]) -> Dict[str, dict]:
    """Each number beside its limit. A number whose limit the configuration's file
    gives as null is read and printed, and decides nothing (PERF.md says why)."""
    compared = {}
    for name, entry in values.items():
        if name not in limits:
            raise KeyError(f"the configuration's file gives no limit for {name}")
        value = entry["value"]
        if limits[name] is None:
            compared[name] = {"value": value, "limit": None, "ok": True, "where": entry["where"]}
            continue
        ok = bool(np.isfinite(value)) and value <= limits[name]
        compared[name] = {"value": value, "limit": limits[name], "ok": ok, "where": entry["where"]}
    return compared
