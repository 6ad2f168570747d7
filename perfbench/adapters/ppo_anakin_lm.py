"""The adapter of the fused on-device PPO loop with a sequence policy (`sheeprl_tpu.cli.run`
-> `algos/ppo/ppo_anakin.py` -> `run_anakin`, `algo.policy=sequence`): the configuration
`lfm2_8b_a1b_ep4` and any other of that loop name this file.

`run_anakin` takes no factories, so the seams are three names of its module, patched for
the length of the run: `build_telemetry` (the harness's tap), `build_sequence_policy`
(the benchmark's weights from the seed in the place of the program's) and
`make_anakin_program` (the fused program, wrapped to copy what its FIRST call produced:
the trajectory its rollout made step by step through the caches, the experts each token
chose, each gradient step's loss parts and sequences, and the leaves' norms of Adam's first
moments and of the parameters' change over the call, taken on the device: the trees are
2.2 GB each and stay there). A cycle is one iteration: one rollout of every env and the
iteration's `update_epochs x minibatches` gradient steps, all in one call.

What is compared (`numbers`), all from that first call:
(a) decoding through the cache against the full forward: the log-probabilities and values
    the rollout computed one token a step, against the reference's forward over the
    recorded tokens;
(b) the update: each gradient step's loss parts, the gradient through Adam's first
    moments after the call's steps (the reference follows every one of them), and the
    parameters' change;
(c) the routing: the share of (token, expert layer) choices that differ from the
    reference's own top-k, and the largest margin between the reference's k-th and
    (k+1)-th score at which one differs. A flip moves a token's output by a whole expert,
    so the reference follows the program's choices downstream: (a) and (b) measure the
    arithmetic, (c) the choices;
(d) the env: the observations, rewards, mask and dones of the recorded trajectory against
    the reference's own recomputation from the episodes' prompts (copied from the env's
    state before the call) and the recorded actions, exactly. The update's inputs are the
    program's own rollout, so this is what holds the reward and the mask to the env's rule.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Dict

import numpy as np

from perfbench.harness.bench import load_file
from perfbench.harness.check import adam_mu, leaf_gaps

LOSSES = ("policy", "value", "entropy")  # the order of the program's loss parts
LM_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads",
           "num_key_value_heads", "num_dense_layers", "num_experts_per_tok", "conv_L_cache")


def leaf_norms(tree):
    """`check.leaf_norms`, traced: the L2 norm of every leaf, still on the device."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda x: jnp.linalg.norm(x.ravel()), tree)


def named(norms) -> Dict[str, float]:
    """A tree of scalar norms on the host, `{leaf's path: norm}` as `check.leaf_gaps` takes them."""
    import jax

    return {jax.tree_util.keystr(path): float(v)
            for path, v in jax.tree_util.tree_flatten_with_path(jax.device_get(norms))[0]}


class Adapter:
    train_module = "anakin_step"  # the fused program's name on the capture's `XLA Modules` line
    reference_block = 8  # sequences the reference computes at a time, so that it fits
    compared = ("rollout_logprob_gap", "rollout_value_gap", "route_mismatch_share", "route_flip_margin",
                "env_mismatch_count", "policy_loss_gap", "value_loss_gap", "entropy_loss_gap",
                "grad_gap", "grad_mid_gap", "update_gap", "update_mid_gap")

    def __init__(self, reference_path: str):
        self.ref = load_file(reference_path)
        self.counters = None  # the last iteration's counters, for `step_flops`

    # -- the configuration -------------------------------------------------------
    def spec(self, cfg) -> dict:
        algo, lm = cfg.algo, cfg.algo.lm
        covered = {
            "sequence policy": str(algo.get("policy")) == "sequence",
            "no schedule": not (algo.anneal_lr or algo.anneal_clip_coef or algo.anneal_ent_coef),
            "plain loss": algo.loss_reduction == "mean" and not algo.clip_vloss and not algo.normalize_advantages,
            "no gradient clip": not algo.max_grad_norm,
            "whole minibatches": int(cfg.env.num_envs) % int(algo.per_rank_batch_size) == 0,
            "the token env": cfg.env.id == "token_copy" and int(cfg.env.tokens.episode_steps) == int(algo.rollout_steps),
        }
        broken = [k for k, ok in covered.items() if not ok]
        if broken:
            raise ValueError(f"the plain reference does not cover this configuration: {broken}")
        return {
            **{k: int(lm[k]) for k in LM_KEYS},
            "head_dim": int(lm.hidden_size) // int(lm.num_attention_heads),
            "vocab_size": int(lm.vocab_size),
            "layer_types": [str(t) for t in lm.layer_types],
            "num_experts_routed": int(lm.num_experts),
            "experts_held": [int(lm.experts_held[0]), int(lm.experts_held[1])],
            "norm_eps": float(lm.norm_eps),
            "rope_theta": float(lm.rope_theta),
            "rollout_steps": int(algo.rollout_steps),
            "num_envs": int(cfg.env.num_envs),
            "prompt": [int(cfg.env.tokens.prompt_min), int(cfg.env.tokens.prompt_max)],
            "minibatch_sequences": int(algo.per_rank_batch_size),
            "update_epochs": int(algo.update_epochs),
            "gamma": float(algo.gamma),
            "gae_lambda": float(algo.gae_lambda),
            "clip_coef": float(algo.clip_coef),
            "vf_coef": float(algo.vf_coef),
            "ent_coef": float(algo.ent_coef),
            "lr": float(algo.optimizer.lr),
            "eps": float(algo.optimizer.eps),
            "precision": str(cfg.fabric.precision),
            "matmul_precision": str(cfg.float32_matmul_precision),
        }

    def cycle(self, cfg):
        """One iteration: a rollout of every env and the iteration's gradient steps."""
        algo, envs = cfg.algo, int(cfg.env.num_envs)
        return 1, int(algo.update_epochs) * (envs // int(algo.per_rank_batch_size)), envs * int(algo.rollout_steps)

    def step_flops(self, m: dict) -> float:
        """Model FLOPs of one whole iteration, rollout and update, the experts by the pairs
        the program counted on its held experts (the expectation where none was read)."""
        from perfbench.harness import lm_flops

        return lm_flops.iteration_flops(m, self.counters)

    # -- the seams ---------------------------------------------------------------
    @contextlib.contextmanager
    def seams(self, run):
        import jax
        import jax.numpy as jnp

        import sheeprl_tpu.algos.ppo.anakin as anakin

        rec = run.recorded
        rec.update(calls=0)
        latest = {}
        run.sync_tree = lambda: latest.get("params")
        run.latest = latest
        program = {name: getattr(anakin, name) for name in ("build_telemetry", "build_sequence_policy", "make_anakin_program")}

        def build_sequence_policy(cfg, vocab_size, key):
            run.stamp("composed")
            policy, theirs = program["build_sequence_policy"](cfg, vocab_size, key)
            # the seed is an argument, not a constant of the program: one cache entry for all seeds
            params = jax.jit(partial(self.ref.init_params, run.model))(np.int32(run.seed))
            shapes = lambda tree: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)  # noqa: E731
            if shapes(params) != shapes(theirs):
                raise ValueError("the reference's weights are not in the program's layout")
            del theirs
            run.device = jax.tree_util.tree_leaves(params)[0].devices().pop()
            run.stamp("agent")
            return policy, params

        def norms_after(seed, mu, params):
            changed = jax.tree_util.tree_map(jnp.subtract, params, self.ref.init_params(run.model, seed))
            return leaf_norms(mu), leaf_norms(changed)

        def make_anakin_program(*args, **kwargs):
            fused, rollout_only, updates = program["make_anakin_program"](*args, **kwargs)

            def checked(params, opt_state, env_state, obs, key, stats, clip_coef, ent_coef):
                run.stamp("prefilled")
                first = rec["calls"] == 0
                if first:  # every rollout begins at a reset: the episodes' prompts, before the call donates them
                    inner = env_state.inner
                    rec["episodes"] = jax.device_get({"t": inner.t, "prompt": inner.prompt, "prompt_len": inner.prompt_len})
                with contextlib.nullcontext() if first else run.train_span():
                    out = fused(params, opt_state, env_state, obs, key, stats, clip_coef, ent_coef)
                latest["params"], latest["counters"] = out[0], out[7]["counters"]
                rec["calls"] += 1
                if first:
                    rec["record"] = jax.device_get(out[7]["record"])
                    rec["clip_coef"], rec["ent_coef"] = float(clip_coef), float(ent_coef)
                    # the leaves' norms of Adam's first moments and of the parameters' change, in
                    # one program that draws the seed's weights again leaf by leaf: no second
                    # copy of a 2.2 GB tree stands on the device or goes to the host
                    rec["mu_norms"], rec["update_norms"] = map(named, jax.jit(norms_after)(
                        np.int32(run.seed), adam_mu(out[1]), out[0]))
                    run.stamp("checked")
                    run.window.arm()
                return out

            checked.lower = fused.lower  # the program's own analysis lowers it
            return checked, rollout_only, updates

        anakin.build_telemetry = run.make_telemetry
        anakin.build_sequence_policy, anakin.make_anakin_program = build_sequence_policy, make_anakin_program
        try:
            yield
        finally:
            for name, original in program.items():
                setattr(anakin, name, original)

    def after_window(self, run) -> None:
        """The last iteration's counters are read for `step_flops`; the loop's frame has
        gone with `StopRun`, and with this the last reference to the program's parameters."""
        import jax

        counters = run.latest.pop("counters", None)
        self.counters = None if counters is None else {k: float(v) for k, v in jax.device_get(counters).items()}
        run.latest.clear()

    # -- the comparison ----------------------------------------------------------
    def run_reference(self, m: dict, seed: int, recorded: dict) -> dict:
        """The reference's side of the first call: its forward over the recorded tokens
        (following the program's expert choices), then every gradient step of the call on
        the recorded sequences, with Adam. All results on the host."""
        import jax
        import jax.numpy as jnp

        ref, rec, block = self.ref, recorded["record"], self.reference_block
        traj = {k: np.swapaxes(np.asarray(v), 0, 1) for k, v in rec["traj"].items()}  # [E, T, ...]
        chosen = traj.get("route_ids")
        with jax.default_matmul_precision("highest"):
            initial = jax.jit(partial(ref.init_params, m))(np.int32(seed))
            forward = jax.jit(lambda p, tokens, ids: ref.forward(p, m, tokens, ids))
            logps, values, owns, margins = [], [], [], []
            for lo in range(0, traj["tokens"].shape[0], block):
                part = slice(lo, lo + block)
                logits, value, own, margin = forward(initial, jnp.asarray(traj["tokens"][part]),
                                                     None if chosen is None else jnp.asarray(chosen[part]))
                logp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                           jnp.asarray(traj["actions"][part])[..., None], axis=-1)[..., 0]
                logps.append(np.asarray(logp)), values.append(np.asarray(value))
                if own is not None:
                    owns.append(np.asarray(own)), margins.append(np.asarray(margin))
            # the update's inputs are the program's own rollout: its values, log-probs, rewards
            to_time = lambda a: jnp.asarray(np.swapaxes(a, 0, 1))  # noqa: E731
            returns, advantages = jax.jit(partial(ref.gae, gamma=m["gamma"], lam=m["gae_lambda"]))(
                to_time(traj["rewards"]), to_time(traj["values"]), to_time(traj["dones"]),
                jnp.zeros((traj["tokens"].shape[0],), jnp.float32))
            data = {k: traj[k] for k in ("tokens", "actions", "logprobs", "mask")}
            data["returns"], data["advantages"] = np.asarray(returns).T, np.asarray(advantages).T
            step = jax.jit(partial(ref.block_grad, m), donate_argnums=(1,))
            adam = jax.jit(ref.adam_step, donate_argnums=(1, 2))
            params, opt, losses, update_owns, update_margins = initial, ref.adam_init(initial), [], [], []
            update_chosen, advantage_scale = rec.get("update_route_ids"), []
            for g, rows in enumerate(np.asarray(rec["sequences"])):
                batch = {k: jnp.asarray(v[rows]) for k, v in data.items()}
                counted = data["mask"][rows].astype(np.float64)
                advantage_scale.append(float(np.sum(np.abs(data["advantages"][rows]) * counted) / max(counted.sum(), 1.0)))
                ids = None if update_chosen is None else jnp.asarray(update_chosen[g])
                grads, parts, own, margin = ref.minibatch_grad(
                    step, params, batch, ids, recorded["clip_coef"], recorded["ent_coef"], block)
                params, opt = adam(params, opt, grads, m["lr"], m["eps"])
                losses.append(np.asarray(parts))
                update_owns.append(own), update_margins.append(margin)
            mu_norms = named(jax.jit(leaf_norms)(opt["mu"]))
            update_norms = named(jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(jnp.subtract, a, b)))(params, initial))
        stack = lambda parts: np.concatenate(parts) if parts else None  # noqa: E731
        episodes = recorded["episodes"]
        return {"mu_norms": mu_norms, "update_norms": update_norms, "losses": np.stack(losses),
                "advantage_scale": np.asarray(advantage_scale),
                "env": ref.copy_env(episodes["prompt"], episodes["prompt_len"], traj["actions"]),
                "logprobs": np.concatenate(logps), "values": np.concatenate(values),
                "own": stack(owns), "margin": stack(margins),
                "update_own": np.stack(update_owns) if update_chosen is not None else None,
                "update_margin": np.stack(update_margins) if update_chosen is not None else None}

    def numbers(self, m: dict, recorded: dict, reference: dict) -> Dict[str, dict]:
        rec = recorded["record"]
        traj = {k: np.swapaxes(np.asarray(v), 0, 1) for k, v in rec["traj"].items()}
        out: Dict[str, dict] = {}
        gap = np.abs(traj["logprobs"].astype(np.float64) - reference["logprobs"])
        out["rollout_logprob_gap"] = {"value": float(gap.max()), "where": f"sequence, step {np.unravel_index(gap.argmax(), gap.shape)}"}
        gap = np.abs(traj["values"].astype(np.float64) - reference["values"])
        scale = max(float(np.sqrt(np.mean(np.square(reference["values"], dtype=np.float64)))), 1e-30)
        out["rollout_value_gap"] = {"value": float(gap.max()) / scale, "where": f"sequence, step {np.unravel_index(gap.argmax(), gap.shape)}"}

        differ = total = 0
        worst = 0.0
        pairs = [(traj.get("route_ids"), reference["own"], reference["margin"]),
                 (rec.get("update_route_ids"), reference["update_own"], reference["update_margin"])]
        for program, own, margin in pairs:
            if program is None:
                continue
            flipped = np.any(np.sort(np.asarray(program), axis=-1) != np.sort(own, axis=-1), axis=-1)
            differ, total = differ + int(flipped.sum()), total + flipped.size
            worst = max(worst, float(np.max(np.where(flipped, margin, 0.0))))
        out["route_mismatch_share"] = {"value": differ / max(total, 1), "where": f"{differ} of {total} (token, layer) choices"}
        out["route_flip_margin"] = {"value": worst, "where": "the widest score margin at which a choice differs"}

        # exact: the env's rule recomputed from the prompts and the actions, and the first state a reset's
        episodes, low, high = recorded["episodes"], *m["prompt"]
        wrong = {k: int(np.sum(traj[k] != v)) for k, v in reference["env"].items()}
        wrong["reset"] = int(np.sum(episodes["t"] != 0) + np.sum((episodes["prompt_len"] < low) | (episodes["prompt_len"] > high))
                             + np.sum((episodes["prompt"] < 0) | (episodes["prompt"] >= m["vocab_size"])))
        out["env_mismatch_count"] = {"value": float(sum(wrong.values())), "where": f"entries that differ: {wrong}"}

        program_losses = np.asarray(rec["losses"], np.float64)
        for i, name in enumerate(LOSSES):
            ref_part = reference["losses"][:, i].astype(np.float64)
            # the first step's policy loss is minus the mean advantage, near 0: its gap is
            # taken against the mean |advantage| of the minibatch's counted steps
            scale = reference["advantage_scale"] if name == "policy" else np.abs(ref_part)
            gaps = np.abs(program_losses[:, i] - ref_part) / np.maximum(scale, 1e-30)
            out[f"{name}_loss_gap"] = {"value": float(gaps.max()), "where": f"gradient step {int(gaps.argmax()) + 1}"}
        # `check.group_gaps`, from the leaves' norms: the gradient through Adam's first moments
        # after the call's steps by the worst and the median leaf, then the parameters' change,
        # leaving out the leaves whose reference gradient is under a thousandth of the median's
        ref_grad = reference["mu_norms"]
        worst, leaf, middle = leaf_gaps(recorded["mu_norms"], ref_grad)
        out["grad_gap"] = {"value": worst, "where": leaf}
        out["grad_mid_gap"] = {"value": middle, "where": "the median leaf"}
        median = float(np.median(list(ref_grad.values())))
        moved = {leaf for leaf, norm in ref_grad.items() if norm >= 1e-3 * median}
        worst, leaf, middle = leaf_gaps(recorded["update_norms"], reference["update_norms"], keep=moved)
        out["update_gap"] = {"value": worst, "where": leaf}
        out["update_mid_gap"] = {"value": middle, "where": "the median leaf"}
        return out
