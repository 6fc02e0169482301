"""Operations the algorithm needs, computed from shapes (never from a cost model).

One multiply-accumulate counts as two operations. Only what the algorithm
requires is counted: the rollout's forward pass, the update's forward and
backward pass (backward = 2 x forward), and the bootstrap forward pass once a
trajectory. What the program recomputes or computes for convenience is not
counted (for IMPALA: the forward pass over every step's `final_obs`, needed
only at the rare truncated step). So `mfu_pct` is the model FLOP/s
utilization of the `on-chip-measurement` guide, section 4, taken end to end:
it is not a kernel's roofline share and says nothing about idle time.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def conv_out(size: int, kernel: int, stride: int) -> int:
    """Output extent of a VALID convolution."""
    return (size - kernel) // stride + 1


def forward_flops(network: dict) -> float:
    """Operations of one forward pass of one observation row."""
    kind = network["kind"]
    if kind == "nature_cnn":
        h, w, c = network["obs_shape"]
        macs = 0
        for cout, k, s in zip(
            network["conv_channels"], network["conv_kernels"],
            network["conv_strides"],
        ):
            h, w = conv_out(h, k, s), conv_out(w, k, s)
            macs += h * w * cout * (k * k * c)
            c = cout
        macs += h * w * c * network["dense"]
        macs += network["dense"] * sum(network["head_widths"])
        return 2.0 * macs
    if kind == "mlp_separate":
        # One torso per head (actor, critic), as MuJoCo PPO is published.
        macs = 0
        for out in network["head_widths"]:
            d = network["obs_dim"]
            for hdim in network["hidden"]:
                macs += d * hdim
                d = hdim
            macs += d * out
        return 2.0 * macs
    raise ValueError(f"flops.py knows no network kind {kind!r}")


def flops_per_decision(network: dict, rollout_steps: int,
                       update_passes: float = 1.0) -> float:
    """Operations one environment step (one agent decision) needs end to end:
    1 forward in the rollout, `update_passes` x (forward + backward = 3
    forwards) in the update, and the bootstrap forward shared by the
    `rollout_steps` steps of a trajectory."""
    fwd = forward_flops(network)
    return fwd * (1.0 + 3.0 * update_passes + 1.0 / rollout_steps)


def peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of `device_kind`; KeyError when unlisted."""
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        peaks = json.load(fh)
    if device_kind not in peaks or device_kind.startswith("_"):
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} in "
            f"benchmark/peaks.json (known: "
            f"{sorted(k for k in peaks if not k.startswith('_'))})"
        )
    return peaks[device_kind]["bf16_tflops"] * 1e12
