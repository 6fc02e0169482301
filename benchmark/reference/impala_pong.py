"""Plain reference for the `impala_pong` configuration.

IMPALA's V-trace actor-critic loss (Espeholt et al. 2018, arXiv:1802.01561,
section 4) over the Nature-DQN torso (Mnih et al. 2015): conv 32 8x8/4,
64 4x4/2, 64 3x3/1, dense 512, a policy head and a value head on one trunk.

Departures from the paper, each because the shipped configuration has it:
- rewards are patched with gamma * V(final_obs) where an episode was cut by
  the time limit and not terminated (time-limit bootstrapping), under the
  learner's critic;
- `done` cuts both the bootstrap and the trace (gamma_t = gamma * (1 - done));
- c_t = lam * min(c_bar, ratio), with lam = 1 as published.
The program also caps the log importance ratio at 20 before `exp`; seeded
random policies never come near it, so the reference does not.

TOLERANCE, and why (the numbers are in benchmark/configs/impala_pong.json).
On a TPU the program's float32 convolutions and matrix multiplications run at
the default precision, which the compiler lowers to bf16 operands with float32
accumulation (the trace shows bf16 convolution inputs); the reference runs at
`highest`. Against the reference's own scale (max |target|, about 1.1 with
random weights) that costs 1.1e-3 to 1.2e-3 on the targets and 1.5e-4 to 2e-4 on
the loss (my chip runs, PR 22, seeds 1 and 2, 256 columns, 7 to 13 episode ends
in the sample). Dropping the `done` mask moves the targets by 4.6e-2 and the
loss by 2.2e-3, so the tolerance (5e-3, 1e-3) sits between with a factor of
four to either side. A bf16 update (`--update-dtype bf16`) lands at 1.1e-3 and
2e-4 as well: the shipped float32 configuration already multiplies in bf16 on
the chip, so no tolerance on targets and loss can tell the two apart (a
finding about the program: PERF.md, Findings, PR 22). The precision the
configuration states (`network.compute_dtype`) is therefore held on the types
of the step program as traced (`harness.narrow_matmuls`): with a bf16 update
the check fails on `conv_general_dilated:bfloat16`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def forward(params, obs, network: dict):
    """(logits [B, A], value [B]) for observations [B, H, W, C]."""
    p = params["params"]
    x = obs.astype(jnp.float32)
    if obs.dtype == jnp.uint8:
        x = x / 255.0
    for i, stride in enumerate(network["conv_strides"]):
        layer = p["torso"][f"conv_{i}"]
        x = jax.lax.conv_general_dilated(
            x, layer["kernel"].astype(jnp.float32), (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
        ) + layer["bias"]
        x = jnp.maximum(x, 0.0)
    x = x.reshape(x.shape[0], -1)
    dense = p["torso"]["Dense_0"]
    x = jnp.maximum(_mm(x, dense["kernel"]) + dense["bias"], 0.0)
    logits = _mm(x, p["policy"]["kernel"]) + p["policy"]["bias"]
    value = (_mm(x, p["value"]["kernel"]) + p["value"]["bias"])[:, 0]
    return logits, value


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def vtrace(target_lp, behaviour_lp, rewards, values, dones, bootstrap,
           gamma, rho_bar, c_bar, lam, use_dones: bool = True):
    """V-trace targets by a reverse Python loop over time. Returns
    (vs [T, E], pg_advantages [T, E]). `use_dones=False` drops the mask:
    only the test that shows the reference bites uses it."""
    T = rewards.shape[0]
    ratio = jnp.exp(target_lp - behaviour_lp)
    rho = jnp.minimum(rho_bar, ratio)
    c = lam * jnp.minimum(c_bar, ratio)
    disc = gamma * (1.0 - dones) if use_dones else jnp.full_like(rewards, gamma)
    vs = [None] * T
    acc = jnp.zeros_like(bootstrap)
    for t in reversed(range(T)):
        v_next = values[t + 1] if t + 1 < T else bootstrap
        delta = rho[t] * (rewards[t] + disc[t] * v_next - values[t])
        acc = delta + disc[t] * c[t] * acc
        vs[t] = values[t] + acc
    pg = [None] * T
    for t in range(T):
        vs_next = vs[t + 1] if t + 1 < T else bootstrap
        pg[t] = rho[t] * (rewards[t] + disc[t] * vs_next - values[t])
    return jnp.stack(vs), jnp.stack(pg)


def loss_and_targets(params, traj: dict, bootstrap_obs, hp: dict,
                     network: dict, use_dones: bool = True) -> dict:
    """The scalar loss and the advantage targets for one [T, E] trajectory.
    `traj` holds obs, action, log_prob (behaviour), reward, done, terminated,
    final_obs; `hp` the configuration file's `algorithm` group."""
    with jax.default_matmul_precision("highest"):
        T, E = traj["reward"].shape
        flat = lambda x: x.reshape(T * E, *x.shape[2:])  # noqa: E731
        logits, values = forward(params, flat(traj["obs"]), network)
        logp_all = logits - jax.scipy.special.logsumexp(
            logits, axis=-1, keepdims=True)
        target_lp = jnp.take_along_axis(
            logp_all, flat(traj["action"]).astype(jnp.int32)[:, None], axis=-1
        )[:, 0].reshape(T, E)
        values = values.reshape(T, E)
        entropy = jnp.mean(-jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1))
        _, bootstrap = forward(params, bootstrap_obs, network)
        _, final_v = forward(params, flat(traj["final_obs"]), network)
        truncated = traj["done"] * (1.0 - traj["terminated"])
        rewards = traj["reward"] + hp["gamma"] * final_v.reshape(T, E) * truncated
        vs, pg = vtrace(
            target_lp, traj["log_prob"], rewards, values, traj["done"],
            bootstrap, hp["gamma"], hp["rho_bar"], hp["c_bar"], hp["lam"],
            use_dones=use_dones,
        )
        pg_loss = -jnp.mean(pg * target_lp)
        v_loss = 0.5 * jnp.mean((values - vs) ** 2)
        loss = pg_loss + hp["value_coef"] * v_loss - hp["entropy_coef"] * entropy
        return {"loss": loss, "pg_advantages": pg, "value_targets": vs}
