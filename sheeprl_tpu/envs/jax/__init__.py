"""On-device environment plane: pure-JAX vectorized environments.

The host plane (``sheeprl_tpu/envs/`` + ``utils/env.py``) steps Python/gymnasium
envs and pays a host<->device handoff per vector step. This plane puts the
environment *inside* JAX — ``reset``/``step`` are pure functions over pytrees —
so the Anakin topology (``algos/ppo/anakin.py``) can fuse rollout + train into
one jitted program over the mesh with zero host transfers in steady state
(Podracer, arxiv 2104.06272).

Select it with ``env.backend=jax`` (see ``howto/jax_envs.md``):

- the Anakin loops (``ppo_anakin``/``a2c_anakin``) consume the pure plane
  directly via :func:`make_jax_env`;
- every host-env loop keeps working through :class:`JaxToGymEnv`, the
  gymnasium adapter ``utils/env.py`` swaps in behind the ``make_env`` factory.
"""

from sheeprl_tpu.envs.jax.base import ActionSpec, EnvSpec, JaxEnv
from sheeprl_tpu.envs.jax.classic import CartPole, Pendulum
from sheeprl_tpu.envs.jax.factory import JAX_ENV_IDS, JaxToGymEnv, make_jax_env, resolve_jax_env
from sheeprl_tpu.envs.jax.gridworld import GridWorld
from sheeprl_tpu.envs.jax.tokens import TokenCopy
from sheeprl_tpu.envs.jax.wrappers import AutoReset, AutoResetState, VmapEnv

__all__ = [
    "ActionSpec",
    "AutoReset",
    "AutoResetState",
    "CartPole",
    "EnvSpec",
    "GridWorld",
    "JAX_ENV_IDS",
    "JaxEnv",
    "JaxToGymEnv",
    "Pendulum",
    "TokenCopy",
    "VmapEnv",
    "make_jax_env",
    "resolve_jax_env",
]
