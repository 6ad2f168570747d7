"""Where Dreamer-V3's player runs (`dreamer_v3.settle_act_placement`, `ActPlacement`).

The coupled loop on ONE accelerator device acts on that device, on the trainer's own
parameter buffers; every other caller of `ActPlacement` keeps the host placement. No
accelerator is attached here, so the cases run on CPU devices that are made to look
like one: a fabric stub whose device reports another platform, and, for whole runs
through the CLI, an `ActPlacement` that takes a real CPU fabric for an accelerator's
and the second virtual CPU device for "the host", so that the two placements land on
different devices and can be told apart.
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3
from sheeprl_tpu.algos.dreamer_v3.dreamer_v3_decoupled import _ChannelTrainer
from sheeprl_tpu.utils.timer import timer
from sheeprl_tpu.utils.utils import ActPlacement


def _select(p):
    return {"world_model": p["world_model"], "actor": p["actor"]}


def _chip_fabric(num_devices: int = 1):
    """What `ActPlacement`, `settle_act_placement` and the trainers read of a fabric,
    with a device that says it is a TPU."""
    return SimpleNamespace(
        device=SimpleNamespace(platform="tpu"),
        num_devices=num_devices,
        world_size=1,
        sharding=lambda *spec: None,
    )


def _params():
    return {
        "world_model": {"w": jnp.arange(6.0).reshape(2, 3)},
        "actor": {"w": jnp.ones((4,))},
        "critic": {"w": jnp.zeros((5,))},
    }


def _train_phase(params, opt_state, moments_state, data, cum_steps, key):
    """Stands in for the donated train program: new buffers for every leaf."""
    return jax.tree_util.tree_map(lambda x: x + 1, params), opt_state, moments_state, {}


@pytest.fixture()
def spans(monkeypatch):
    """The spans and counters the timer recorded during the test."""
    monkeypatch.setattr(timer, "disabled", False)
    monkeypatch.setattr(timer, "counters", {})
    timer.ring.clear()
    return lambda: {name for name, *_ in timer.ring}


@pytest.mark.parametrize("case", ["inline", "inline_mesh", "channel", "bare"])
def test_only_the_inline_trainer_on_one_device_aliases_its_buffers(case, spans):
    fabric = _chip_fabric(num_devices=4 if case == "inline_mesh" else 1)
    act = ActPlacement(fabric, _select)
    trainer_args = dict(
        fabric=fabric, cfg=None, act=act, train_phase=_train_phase, params=_params(), opt_state={}, moments_state={}
    )
    data = {"rewards": np.zeros((1, 1, 1, 1), np.float32)}
    if case == "bare":  # what the thirteen other loop files build: nothing settles it
        params = _params()
        view, own = act.view(params), _select(params)
    elif case == "channel":
        trainer = _ChannelTrainer(**trainer_args, multi_process=False, protocol_done={})
        dv3.settle_act_placement(act, trainer, fabric)
        view, _ = trainer.train(data, 0, jax.random.PRNGKey(0), False, False)
        own = _select(trainer.close()[0])  # the learner's final state, as host arrays
    else:
        trainer = dv3._InlineTrainer(**trainer_args)
        dv3.settle_act_placement(act, trainer, fabric)
        view, _ = trainer.train(data, 0, jax.random.PRNGKey(0), False, False)
        own = _select(trainer.params)

    pairs = list(zip(jax.tree_util.tree_leaves(view), jax.tree_util.tree_leaves(own)))
    assert len(pairs) == 2
    for got, mine in pairs:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(mine))
    count, copied = timer.counters["act_view_bytes"]
    if case == "inline":
        assert act.aliased and not act.on_cpu
        assert all(got is mine for got, mine in pairs)
        assert (count, copied) == (1, 0)
        assert "act_view" in spans() and not {"act_view.fetch", "act_view.place"} & spans()
    else:  # pinned: the host placement, byte for byte
        assert act.on_cpu and not act.aliased
        assert not any(got is mine for got, mine in pairs)
        assert all(got.devices() == {act.cpu_device} for got, _ in pairs)
        assert (count, copied) == (1, sum(np.asarray(mine).nbytes for _, mine in pairs))
        assert {"act_view", "act_view.fetch", "act_view.place"} <= spans()


def test_a_cpu_fabric_stays_the_identity_when_settled():
    """`run_dreamer` settles every inline run, CPU ones too: nothing changes there
    (no span, no counter, the key is handed back as it came)."""
    from sheeprl_tpu.analysis.programs import tiny_fabric

    fabric = tiny_fabric()
    act = ActPlacement(fabric, _select)
    trainer = dv3._InlineTrainer(
        fabric=fabric, cfg=None, act=act, train_phase=_train_phase, params=_params(), opt_state={}, moments_state={}
    )
    dv3.settle_act_placement(act, trainer, fabric)
    assert not act.on_cpu and not act.aliased
    key = jax.random.PRNGKey(0)
    assert act.place(key) is key


class _AsIfOnAChip(ActPlacement):
    """A real CPU fabric taken for an accelerator's, with the SECOND virtual CPU
    device as "the host": the host placement lands the view, the key and so the
    player's carry on cpu:1, the aliased one leaves them on cpu:0 with the trainer."""

    def __init__(self, fabric, select=None):
        super().__init__(fabric, select)
        self.on_cpu = True
        self.cpu_device = jax.local_devices(backend="cpu")[1]


_TINY = [  # on top of conftest's `standard_args`; the widths are test_algos.py's
    "dry_run=False",
    "env=dummy",
    "env.id=discrete_dummy",
    "checkpoint.every=0",
    "buffer.size=64",
    # two iterations, a train call in each, then the test episode on the last view
    "algo.total_steps=4",
    "algo.learning_starts=0",
    "algo.replay_ratio=1",
    "algo.per_rank_batch_size=1",
    "algo.per_rank_sequence_length=1",
    "algo.horizon=8",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.discrete_size=4",
    "algo.world_model.stochastic_size=4",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.cnn_keys.encoder=[rgb]",
    "algo.cnn_keys.decoder=[rgb]",
    "algo.mlp_keys.encoder=[state]",
    "algo.mlp_keys.decoder=[state]",
]
_ODV3 = [
    "algo.world_model.cbm_model.n_concepts=3",
    "algo.world_model.cbm_model.concept_bins=[2,2,2]",
    "algo.world_model.cbm_model.emb_size=4",
]


@pytest.mark.parametrize("exp", ["dreamer_v3", "offline_dreamer"])
def test_coupled_run_acts_beside_the_trainer_through_two_train_calls(exp, standard_args, monkeypatch):
    """Set-up, two train calls and the test episode on the aliased path: the view,
    the key and the player's three carries share the trainer's device set at every
    `get_actions`, never turn from uncommitted to committed, and no donated (stale)
    view is ever read."""
    from sheeprl_tpu.algos.dreamer_v3.agent import PlayerDV3
    from sheeprl_tpu.algos.offline_dreamer.agent import PlayerODV3
    from sheeprl_tpu.cli import run

    seen = {"acts": [], "trains": 0, "committed": set()}
    trainer_devices = jax.local_devices(backend="cpu")[0:1]
    player_cls = PlayerDV3 if exp == "dreamer_v3" else PlayerODV3
    original = player_cls.get_actions

    def get_actions(self, params, obs, key, greedy=False):
        placed = [*jax.tree_util.tree_leaves(params), key, self.actions, self.recurrent_state, self.stochastic_state]
        seen["acts"].append({d for x in placed for d in x.devices()})
        seen["committed"].add(tuple(x.committed for x in placed))
        return original(self, params, obs, key, greedy)

    class CountingTrainer(dv3._InlineTrainer):
        def train(self, *args, **kwargs):
            seen["trains"] += 1
            view, metrics = super().train(*args, **kwargs)
            mine = jax.tree_util.tree_leaves(_select(self.params))
            assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(view), mine))
            return view, metrics

    monkeypatch.setattr(player_cls, "get_actions", get_actions)
    monkeypatch.setattr(dv3, "ActPlacement", _AsIfOnAChip)
    monkeypatch.setattr(dv3, "_InlineTrainer", CountingTrainer)
    run([f"exp={exp}", *standard_args, *_TINY, *(_ODV3 if exp == "offline_dreamer" else [])])

    assert seen["trains"] == 2
    assert len(seen["acts"]) >= 3  # one per iteration, and the test episode
    assert all(devices == set(trainer_devices) for devices in seen["acts"])
    # a jit compiles anew when an argument turns from uncommitted to committed: one
    # committed key would do that to the carry after the first step, and so to the
    # reset program in mid-run (it cost the first chip runs of PR 28 their window)
    assert len(seen["committed"]) == 1
