from actor_critic_tpu.envs.jax_env import EnvSpec, JaxEnv, StepOutput, auto_reset
from actor_critic_tpu.envs.acrobot import make_acrobot
from actor_critic_tpu.envs.cartpole import make_cartpole
from actor_critic_tpu.envs.maze import make_maze
from actor_critic_tpu.envs.mixture import MixtureEnv, make_mixture, parse_mixture_spec
from actor_critic_tpu.envs.pendulum import make_pendulum
from actor_critic_tpu.envs.pong import make_pong
from actor_critic_tpu.envs.token_task import make_token_task
from actor_critic_tpu.envs.testbeds import (
    make_bandit,
    make_point_mass,
    make_two_state_mdp,
)

__all__ = [
    "EnvSpec",
    "JaxEnv",
    "MixtureEnv",
    "StepOutput",
    "auto_reset",
    "make_acrobot",
    "make_bandit",
    "make_cartpole",
    "make_maze",
    "make_mixture",
    "make_pendulum",
    "make_point_mass",
    "make_pong",
    "make_token_task",
    "make_two_state_mdp",
    "parse_mixture_spec",
]
