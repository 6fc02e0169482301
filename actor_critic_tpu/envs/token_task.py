"""A token-level episodic task: the action is the next token id.

`jax:token_task`. An episode is one row of `horizon` token positions: a
seeded prompt, then the policy's own tokens. The programmatic reward is the
copy task of RL post-training smoke tests: the response should repeat the
prompt cyclically, and the share of response tokens that do is paid once, at
the last response token.

Protocol (the JaxEnv conventions, with two readings of its own):
- obs = int32 `(token id, position, is_prompt)`: the token the policy reads
  at this step, its position in the row, and 1 where the env will IGNORE the
  action taken on it: the next token is a prompt token, or the row is padding
  after EOS. A policy masks those steps out of its loss.
- While `position + 1 < prompt_len` the env feeds the next prompt token. After
  that the action is the next token. The episode ends (`done` = `terminated`
  = 1, never truncated) at the step whose action is EOS, or at position
  `horizon - 1`; after an EOS the row is padding (the EOS id, `is_prompt` 1,
  reward 0, no second `done`) until position `horizon - 1`, where every row
  resets. So each row of a `[horizon, E]` unroll that starts at a reset holds
  exactly one episode, and all rows share their position: what a policy that
  carries a cache through the rollout relies on (`models/seq_policy.py`).
- Prompt tokens are uniform over the ids other than EOS, prompt lengths
  log-uniform in `[prompt_min, prompt_max]`, both from the episode's key.
- `prefill_len` = P (at most `prompt_min`, so the first P tokens of every row
  are prompt; 0: none) is how much of the prompt `prefill` hands out at once,
  for a policy that takes it in one pass (`common.rollout_scan`): the row's
  first P observations, and the state at the last of them. The action on that
  one is the first the env may read (it does where the prompt is P long).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from actor_critic_tpu.envs.jax_env import EnvSpec, JaxEnv, StepOutput


class TokenTaskState(NamedTuple):
    prompt: jax.Array      # [prompt_max] int32, valid up to prompt_len
    prompt_len: jax.Array  # int32
    position: jax.Array    # int32, of the token the policy reads next
    token: jax.Array       # int32, that token
    matches: jax.Array     # int32, response tokens that repeated the prompt
    finished: jax.Array    # bool, an EOS ended the episode: the rest is padding
    key: jax.Array


def make_token_task(
    vocab_size: int = 16160,
    horizon: int = 512,
    prompt_min: int = 16,
    prompt_max: int = 128,
    eos_id: int = 0,
    prefill_len: int = 0,
) -> JaxEnv:
    if not 1 <= prompt_min <= prompt_max < horizon:
        raise ValueError(
            f"need 1 <= prompt_min <= prompt_max < horizon, got "
            f"{prompt_min}, {prompt_max}, {horizon}"
        )
    if not 0 <= prefill_len <= prompt_min:
        raise ValueError(
            f"prefill_len={prefill_len} must be at most prompt_min="
            f"{prompt_min}: only prompt tokens are known before the policy acts"
        )
    if vocab_size < 2 or not 0 <= eos_id < vocab_size:
        raise ValueError(f"bad vocab_size / eos_id: {vocab_size}, {eos_id}")

    def obs_of(s: TokenTaskState) -> jax.Array:
        ignored = (s.position + 1 < s.prompt_len) | s.finished
        return jnp.stack(
            [s.token, s.position, ignored.astype(jnp.int32)]
        ).astype(jnp.int32)

    def reset(key):
        key, k_len, k_tok = jax.random.split(key, 3)
        # Log-uniform: prompt_min * ratio ** u, u uniform in [0, 1).
        ratio = (prompt_max + 1.0) / prompt_min
        length = prompt_min * ratio ** jax.random.uniform(k_len, (), jnp.float32)
        prompt_len = jnp.clip(
            jnp.floor(length).astype(jnp.int32), prompt_min, prompt_max
        )
        # Uniform over the ids other than EOS.
        draw = jax.random.randint(k_tok, (prompt_max,), 0, vocab_size - 1)
        prompt = (draw + (draw >= eos_id)).astype(jnp.int32)
        state = TokenTaskState(
            prompt=prompt,
            prompt_len=prompt_len,
            position=jnp.zeros((), jnp.int32),
            token=prompt[0],
            matches=jnp.zeros((), jnp.int32),
            finished=jnp.zeros((), jnp.bool_),
            key=key,
        )
        return state, obs_of(state)

    def step(state: TokenTaskState, action) -> StepOutput:
        action = action.astype(jnp.int32)
        nxt = state.position + 1
        live = (nxt >= state.prompt_len) & ~state.finished
        # The response's j-th token should be prompt[j mod prompt_len].
        j = nxt - state.prompt_len
        target = state.prompt[jnp.clip(j, 0) % state.prompt_len]
        matches = state.matches + (live & (action == target)).astype(jnp.int32)
        last = state.position == horizon - 1
        eos = live & (action == eos_id)
        ends = live & (eos | last)
        reward = jnp.where(
            ends, matches.astype(jnp.float32) / jnp.maximum(j + 1, 1), 0.0
        )
        finished = state.finished | eos
        token = jnp.where(
            nxt < state.prompt_len,
            state.prompt[jnp.clip(nxt, 0, prompt_max - 1)],
            jnp.where(finished, eos_id, action),
        )
        moved = state._replace(
            position=nxt, token=token, matches=matches, finished=finished
        )
        reset_key, _ = jax.random.split(state.key)
        fresh, _ = reset(reset_key)
        out_state = jax.tree.map(
            lambda a, b: jnp.where(last, a, b), fresh, moved
        )
        done = ends.astype(jnp.float32)
        return StepOutput(
            state=out_state,
            obs=obs_of(out_state),
            reward=reward,
            done=done,
            info={"terminated": done, "final_obs": obs_of(moved)},
        )

    def prefill(state: TokenTaskState):
        position = jnp.arange(prefill_len, dtype=jnp.int32)
        obs = jnp.stack(
            [state.prompt[:prefill_len], position,
             (position + 1 < state.prompt_len).astype(jnp.int32)], axis=-1)
        return state._replace(
            position=position[-1], token=state.prompt[prefill_len - 1]), obs

    return JaxEnv(
        spec=EnvSpec(
            obs_shape=(3,), action_dim=vocab_size, discrete=True,
            obs_dtype=jnp.int32, can_truncate=False, episode_horizon=horizon,
            prefill_len=prefill_len,
        ),
        reset=reset,
        step=step,
        prefill=prefill if prefill_len else None,
    )
