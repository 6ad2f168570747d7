"""The posterior scan's backward pass (``DV3Agent.dynamic_scan``): the gradients of the
kernels its step multiplies by are formed once, after the loop, and what never fed the
carry is computed outside it. Held here against the scan as it was before (one
``lax.scan`` with everything in its step, differentiated by plain autodiff), which the
file keeps as the independent form: same outputs, the same gradient for every leaf,
and a compiled backward loop that computes nothing of a kernel's shape."""

from __future__ import annotations

import re

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from sheeprl_tpu.algos.dreamer_v3.agent import DV3Agent, build_agent, unimix_logits
from sheeprl_tpu.config.composer import compose
from sheeprl_tpu.parallel.fabric import Fabric
from sheeprl_tpu.utils.timer import timer

T, B, ACTIONS = 6, 3, 6
# every width differs from every other and from T and B, so a shape names its array
H, REC_UNITS, REP_UNITS, TRANS_UNITS, STOCH, DISCRETE, EMBED = 24, 12, 20, 28, 4, 5, 256


def _agent(decoupled: bool, precision: str = "32-true"):
    cfg = compose(
        [
            "exp=dreamer_v3",
            "env=dummy",
            "env.id=discrete_dummy",
            f"algo.world_model.discrete_size={DISCRETE}",
            f"algo.world_model.stochastic_size={STOCH}",
            "algo.world_model.encoder.cnn_channels_multiplier=2",
            f"algo.world_model.recurrent_model.recurrent_state_size={H}",
            f"algo.world_model.recurrent_model.dense_units={REC_UNITS}",
            f"algo.world_model.transition_model.hidden_size={TRANS_UNITS}",
            f"algo.world_model.representation_model.hidden_size={REP_UNITS}",
            f"algo.world_model.decoupled_rssm={decoupled}",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.cnn_keys.encoder=[rgb]",
            "algo.cnn_keys.decoder=[rgb]",
            "algo.mlp_keys.encoder=[]",
            "algo.mlp_keys.decoder=[]",
        ]
    )
    fabric = Fabric(devices=1, accelerator="cpu", precision=precision)
    fabric._setup()
    obs_space = gym.spaces.Dict({"rgb": gym.spaces.Box(0, 255, (3, 64, 64), np.uint8)})
    agent, params = build_agent(fabric, (ACTIONS,), False, cfg, obs_space, jax.random.PRNGKey(0), None)
    # a learnable initial state away from 0, so that its gradient is not a corner case
    wm = {**params["world_model"], "initial_recurrent_state": jnp.linspace(-1.0, 1.0, H)}
    return agent, wm, fabric.compute_dtype


def _inputs(dtype=jnp.float32):
    embedded = jax.random.normal(jax.random.PRNGKey(1), (T, B, EMBED)).astype(dtype)
    actions = jax.nn.one_hot(jax.random.randint(jax.random.PRNGKey(2), (T, B), 0, ACTIONS), ACTIONS)
    # an episode starts in mid-sequence in one row: the initial state enters the carry there
    is_first = jnp.zeros((T, B, 1)).at[0].set(1.0).at[3, 1].set(1.0)
    return embedded, actions, is_first, jax.random.PRNGKey(3)


def scan_as_it_was(self, wm_params, embedded, actions, is_first, key):
    """``dynamic_scan`` before its backward pass was taken apart: prior, posterior and
    recurrent update all in the step, the kernels' gradients left to ``lax.scan``."""
    T, B = embedded.shape[:2]
    h0, z0 = self.initial_state(wm_params, (B,))
    keys = jax.random.split(key, T)
    actions = actions.astype(embedded.dtype)
    is_first = is_first.astype(embedded.dtype)
    h0, z0 = h0.astype(embedded.dtype), z0.astype(embedded.dtype)
    init = (
        jnp.zeros((B, self.recurrent_state_size), embedded.dtype),
        jnp.zeros((B, self.stoch_state_size), embedded.dtype),
    )

    def _recurrent_prior(h, z_prev, a, first):
        a = (1 - first) * a
        h = (1 - first) * h + first * h0
        z_prev = (1 - first) * z_prev + first * z0
        h = self._recurrent(wm_params, z_prev, a, h)
        prior_logits = self.transition_model.apply({"params": wm_params["transition_model"]}, h)
        return h, unimix_logits(prior_logits, self.discrete_size, self.unimix)

    if self.decoupled_rssm:
        post_logits_all, zs_all = jax.vmap(lambda e, k: self._representation(wm_params, h0, e, k))(
            embedded, keys
        )

        def step(carry, inp):
            h, z_prev = carry
            a, z_t, post_logits_t, first = inp
            h, prior_logits = _recurrent_prior(h, z_prev, a, first)
            return (h, z_t), (h, z_t, post_logits_t, prior_logits)

        xs = (actions, zs_all, post_logits_all, is_first)
    else:

        def step(carry, inp):
            h, z = carry
            a, e, first, k = inp
            h, prior_logits = _recurrent_prior(h, z, a, first)
            post_logits, z = self._representation(wm_params, h, e, k)
            return (h, z), (h, z, post_logits, prior_logits)

        xs = (actions, embedded, is_first, keys)
    return jax.lax.scan(step, init, xs)[1]


def _value_and_grad(scan, actions, is_first, key):
    """A scalar of all four outputs, each under its own fixed random weights, with its
    gradient with respect to the world model and to ``embedded``."""
    shapes = [(T, B, H), (T, B, STOCH * DISCRETE), (T, B, STOCH * DISCRETE), (T, B, STOCH * DISCRETE)]
    weights = [jax.random.normal(jax.random.PRNGKey(10 + i), s) for i, s in enumerate(shapes)]

    def scalar(wm, embedded):
        outs = scan(wm, embedded, actions, is_first, key)
        return sum(jnp.sum(o.astype(jnp.float32) * w) for o, w in zip(outs, weights)), outs

    return jax.jit(jax.value_and_grad(scalar, argnums=(0, 1), has_aux=True))


@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled_rssm"])
def test_every_gradient_equals_plain_autodiff_through_the_scan_as_it_was(decoupled):
    agent, wm, _ = _agent(decoupled)
    embedded, actions, is_first, key = _inputs()
    (_, ref_outs), ref_grads = _value_and_grad(
        lambda *a: scan_as_it_was(agent, *a), actions, is_first, key
    )(wm, embedded)
    (_, outs), grads = _value_and_grad(agent.dynamic_scan, actions, is_first, key)(wm, embedded)

    for name, ref, out in zip(("hs", "zs", "post_logits", "prior_logits"), ref_outs, outs):
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5, err_msg=name)
    ref_leaves = jax.tree_util.tree_leaves_with_path(ref_grads)
    leaves = jax.tree_util.tree_leaves(grads)
    assert len(leaves) == len(ref_leaves)
    moved = set()
    for (path, ref), got in zip(ref_leaves, leaves):
        name = jax.tree_util.keystr(path)
        assert got.shape == ref.shape and got.dtype == ref.dtype, name
        scale = max(float(jnp.abs(ref).max()), 1.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5 * scale, rtol=0, err_msg=name)
        if float(jnp.abs(ref).max()) > 0:
            moved.add(name)
    # the comparison is not of zeros: every leaf of the three models, the learnable
    # initial state (through h0 and, by z0, the transition model) and embedded move
    rssm = [
        jax.tree_util.keystr(path)
        for path, _ in ref_leaves
        if any(m in jax.tree_util.keystr(path) for m in ("recurrent_model", "representation_model", "transition_model"))
    ]
    assert set(rssm) <= moved and "[0]['initial_recurrent_state']" in moved and "[1]" in moved


def test_bf16_mixed_keeps_the_carrys_dtype_and_float32_gradient_leaves():
    agent, wm, dtype = _agent(False, precision="bf16-mixed")
    assert dtype == jnp.bfloat16
    embedded, actions, is_first, key = _inputs(dtype)
    (_, outs), (wm_grads, embedded_grad) = _value_and_grad(agent.dynamic_scan, actions, is_first, key)(
        wm, embedded
    )
    assert all(o.dtype == jnp.bfloat16 for o in outs)
    assert embedded_grad.dtype == jnp.bfloat16
    for path, leaf in jax.tree_util.tree_leaves_with_path(wm_grads):
        assert leaf.dtype == jnp.float32, jax.tree_util.keystr(path)
        assert bool(jnp.all(jnp.isfinite(leaf))), jax.tree_util.keystr(path)
    for model in ("recurrent_model", "representation_model", "transition_model"):
        for path, leaf in jax.tree_util.tree_leaves_with_path(wm_grads[model]):
            assert float(jnp.abs(leaf).max()) > 0, (model, jax.tree_util.keystr(path))


# -- structure of the compiled backward pass -------------------------------------------


def _computations(hlo_text: str):
    comps, current = {}, None
    for line in hlo_text.splitlines():
        header = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if header:
            current = comps.setdefault(header.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            current.append(line)
    return comps


def _computed_in_while_bodies(hlo_text: str, shape):
    """Instructions of ``shape`` inside any ``while`` body (and what it calls) that
    compute something: reading a loop-invariant operand (parameter, tuple element,
    bitcast) is what a step that multiplies by a kernel has to do."""
    comps = _computations(hlo_text)
    reached, todo = set(), list(re.findall(r"body=%?([\w.\-]+)", hlo_text))
    assert todo, "no while loop in the compiled program"
    while todo:
        name = todo.pop()
        if name in reached or name not in comps:
            continue
        reached.add(name)
        for line in comps[name]:
            for called in re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line):
                todo.append(called)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                todo.extend(c.strip().lstrip("%") for c in group.split(","))
    dims = ",".join(str(d) for d in shape)
    result = re.compile(r"= \(?[a-z0-9]+\[" + dims + r"\](?:\{[^}]*\})? (?!parameter|get-tuple-element|bitcast)")
    return [line.strip() for name in reached for line in comps[name] if result.search(line)]


KERNEL_SHAPES = {
    "the GRU's joint kernel": (REC_UNITS + H, 3 * H),
    "the representation model's first kernel": (H + EMBED, REP_UNITS),
    "its rows for h": (H, REP_UNITS),
    "the transition model's first kernel": (H, TRANS_UNITS),
    "the transition model's head": (TRANS_UNITS, STOCH * DISCRETE),
}


def _compiled_gradient_text(scan):
    agent, wm, _ = _agent(False)
    embedded, actions, is_first, key = _inputs()

    def scalar(wm, embedded):
        return sum(jnp.sum(o**2) for o in scan(agent, wm, embedded, actions, is_first, key))

    return jax.jit(jax.grad(scalar, argnums=(0, 1))).lower(wm, embedded).compile().as_text()


def test_no_loop_of_the_compiled_gradient_computes_anything_of_a_kernels_shape():
    text = _compiled_gradient_text(DV3Agent.dynamic_scan)
    for what, shape in KERNEL_SHAPES.items():
        assert _computed_in_while_bodies(text, shape) == [], what
    # the reader does see them where they are: the scan as it was sums each kernel's
    # gradient in its backward loop
    text = _compiled_gradient_text(scan_as_it_was)
    for what in ("the GRU's joint kernel", "the representation model's first kernel", "the transition model's first kernel"):
        assert _computed_in_while_bodies(text, KERNEL_SHAPES[what]) != [], what


def test_the_counters_say_where_each_kernels_gradient_is_formed(monkeypatch):
    monkeypatch.setattr(timer, "disabled", False)
    monkeypatch.setattr(timer, "counters", {})
    agent, wm, _ = _agent(False)
    embedded, actions, is_first, key = _inputs()
    kernels = sum(
        leaf.nbytes
        for model in ("recurrent_model", "representation_model", "transition_model")
        for path, leaf in jax.tree_util.tree_leaves_with_path(wm[model])
        if "kernel" in jax.tree_util.keystr(path)
    )

    outs = jax.jit(agent.dynamic_scan)(wm, embedded, actions, is_first, key)
    assert timer.counters["rssm/weight_grad_bytes_in_scan"] == [1, 0.0]
    assert timer.counters["rssm/weight_grad_bytes_hoisted"] == [1, float(kernels)]

    # the sequence-parallel unroll runs the same step (equal outputs) under plain
    # autodiff, and says so: the step's four kernels, W[:H] of the posterior's first
    timer.counters.clear()
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("seq",))
    sp_outs = jax.jit(lambda *a: agent.dynamic_scan_sp(*a, mesh))(wm, embedded, actions, is_first, key)
    for out, sp in zip(outs, sp_outs):
        np.testing.assert_allclose(np.asarray(sp), np.asarray(out), rtol=1e-5, atol=1e-6)
    in_scan = 4 * ((STOCH * DISCRETE + ACTIONS) * REC_UNITS + (REC_UNITS + H) * 3 * H + H * REP_UNITS + REP_UNITS * STOCH * DISCRETE)
    assert timer.counters["rssm/weight_grad_bytes_in_scan"] == [1, float(in_scan)]
    assert timer.counters["rssm/weight_grad_bytes_hoisted"] == [1, float(kernels - in_scan)]
