"""Host-side numpy mirror of the `replay/quantize.py` codecs, plus the
per-key codec specs for trajectory blocks (ISSUE 13).

The device trajectory ring (`data_plane/ring.py`) moves the encode to
the PRODUCER side: actor threads quantize each collected numpy block on
the host and put only the encoded bytes to the device — int8 obs cross
to the device at a quarter of the fp32 bytes, and the learner's in-jit
decode reads them back through the SAME stats the host encoded with
(they ride the ring state next to the storage). That demands a numpy
implementation of `quantize.encode`/`update_stats`: calling the jnp
versions from an actor thread would dispatch a device program per block
— the exact host↔device chatter the data plane exists to remove.

Consistency contract: encode (host numpy, these functions) and decode
(device, `quantize.decode`) always use ONE stats tree — the host
computes it, uploads it with every enqueue while calibrating, and
freezes it after `quantize.CALIBRATION_TRANSITIONS` transitions exactly
like the replay ring's device-side stats. Host/device float divergence
is therefore impossible by construction (nothing is computed twice);
tests/test_data_plane.py pins the round-trip error bounds to the
quantize table regardless.

Codec specs (`traj_codecs`) key on block-array NAMES, not tree
positions, because trajectory blocks are plain dicts whose key set
varies by algorithm and correction mode:

- observation-family keys (obs / final_obs / last_obs / next_obs) carry
  the bulk of every block's bytes and quantize well (f16, or calibrated
  i8);
- reward quantizes as calibrated i8 in the aggressive mode;
- done / terminated are exact {0,1} flags (bool8);
- action, log_prob, value, final_values, bootstrap_value stay raw:
  behavior log-probs feed the V-trace importance ratios and the
  recorded value is the clip anchor — quantizing either biases the
  correction itself, the one unsafe default (the `replay/quantize.py`
  action rationale, applied to the on-policy block).
"""

from __future__ import annotations

from typing import Any

import numpy as np

from actor_critic_tpu.replay import quantize

# Block keys treated as observations by the trajectory-codec presets.
OBS_KEYS = ("obs", "final_obs", "last_obs", "next_obs")
# Keys that must never quantize (see module docstring).
RAW_KEYS = ("action", "log_prob", "value", "final_values", "bootstrap_value")
TRAJ_MODES = ("fp32", "f16", "int8")

_EPS = quantize._EPS
_MEAN_SATURATE = quantize._MEAN_SATURATE


def traj_codecs(mode: str, block_spec: dict[str, Any]) -> dict[str, str]:
    """Per-key codec-kind dict for a trajectory block shaped like
    `block_spec` (any mapping of name → array-like with a dtype).

    `fp32` is all-raw (the bitwise-equivalence mode); `f16` halves the
    observation bytes; `int8` additionally standardizes observations and
    rewards to calibrated int8 and packs the flags (the smallest
    enqueue, ~4x on the obs-dominated leaves).
    """
    if mode not in TRAJ_MODES:
        raise ValueError(
            f"data-plane codec must be one of {TRAJ_MODES}, got {mode!r}"
        )
    out: dict[str, str] = {}
    for name, leaf in block_spec.items():
        dtype = np.dtype(getattr(leaf, "dtype", np.float32))
        if mode == "fp32" or name in RAW_KEYS or dtype != np.float32:
            # Non-float leaves (discrete int actions, uint8 pixel obs)
            # pass through: uint8 is already dense and int actions are
            # exact by requirement.
            out[name] = "raw"
        elif name in OBS_KEYS:
            out[name] = "f16" if mode == "f16" else "i8"
        elif name == "reward":
            out[name] = "i8" if mode == "int8" else "raw"
        elif name in ("done", "terminated"):
            out[name] = "bool8" if mode == "int8" else "raw"
        else:
            out[name] = "raw"
    return out


# ---------------------------------------------------------------------------
# numpy stats (calibrate-then-freeze, mirroring quantize.update_stats)
# ---------------------------------------------------------------------------

def np_init_stats(kind: str, item_shape: tuple[int, ...]) -> dict:
    """Zeroed numpy stats slot, same shape policy as quantize.init_stats
    (item-shaped mean/scale for `i8`, scalar placeholders otherwise,
    scale seeded at the _EPS floor)."""
    shape = tuple(item_shape) if kind in quantize.STAT_KINDS else ()
    return {
        "mean": np.zeros(shape, np.float32),
        "scale": np.full(shape, _EPS, np.float32),
        "count": np.zeros((), np.int32),
    }


def np_update_stats(
    kind: str, stats: dict, batch: np.ndarray,
    num_transitions: int | None = None,
) -> dict:
    """Fold one batch into the running stats (no-op for stat-free
    codecs): cumulative-average mean + monotone running-max scale, both
    FROZEN once `quantize.CALIBRATION_TRANSITIONS` transitions have been
    absorbed — the replay ring's calibrate-then-freeze contract, on the
    host.

    `num_transitions` is how many TRANSITIONS this batch represents —
    the unit the freeze threshold is defined in (`quantize.QuantStats`:
    "transitions absorbed"). The ring's stats are scalar-shaped, so the
    default element count would inflate a [K, E, obs_dim] block by the
    feature dim and freeze the calibration window obs_dim× too early
    (before the random warmup the freeze rationale depends on);
    `DeviceTrajRing` passes the per-key transition count derived from
    its block layout. With a constant feature size per key,
    transition-weighting and element-weighting produce the identical
    cumulative mean — only the freeze clock differs."""
    if kind not in quantize.STAT_KINDS:
        return stats
    count = int(stats["count"])
    if count >= quantize.CALIBRATION_TRANSITIONS:
        return stats  # frozen
    x = np.asarray(batch, np.float32)
    item_ndim = stats["mean"].ndim
    axes = tuple(range(x.ndim - item_ndim))
    b = 1
    for a in axes:
        b *= x.shape[a]
    n = b if num_transitions is None else int(num_transitions)
    w = np.float32(n) / np.float32(max(count + n, 1))
    mean = (stats["mean"] + (x.mean(axis=axes, dtype=np.float32)
                             - stats["mean"]) * w).astype(np.float32)
    absmax = np.abs(x - mean).max(axis=axes).astype(np.float32)
    scale = np.maximum(np.maximum(stats["scale"], absmax),
                       np.float32(_EPS))
    return {
        "mean": mean,
        "scale": scale,
        "count": np.asarray(min(count + n, _MEAN_SATURATE), np.int32),
    }


# jaxlint: disable=precision-discipline (audited fork: numpy twin of
# quantize.encode — same storage-dtype-forks-on-kind contract, same
# ring-allocated-with-the-same-kind consumer guarantee)
def np_encode(kind: str, stats: dict, x: np.ndarray) -> np.ndarray:
    """One host leaf → its stored representation (numpy twin of
    quantize.encode; the device decodes with the same stats).

    Saturates exactly like the device codec (see `quantize.encode`):
    out-of-range values clip to the representable range before the
    narrowing cast (an unclipped float→int8 cast WRAPS; float16
    overflows to inf); NaN narrows deterministically through nan_to_num
    on the int8 paths and propagates verbatim through f16 — identity
    for every finite in-range value, so the host-encode ==
    device-encode bit-exactness contract is unchanged."""
    if kind == "raw":
        return np.asarray(x)
    if kind == "f16":
        f16_max = float(np.finfo(np.float16).max)
        return np.clip(x, -f16_max, f16_max).astype(np.float16)
    if kind == "bool8":
        return np.round(
            np.clip(np.nan_to_num(x), 0.0, 1.0)
        ).astype(np.int8)
    if kind == "i8_unit":
        q = np.clip(
            np.nan_to_num(np.asarray(x, np.float32)), -1.0, 1.0
        ) * 127.0
        return np.round(q).astype(np.int8)
    if kind == "i8":
        z = (np.asarray(x, np.float32) - stats["mean"]) / stats["scale"]
        return np.round(
            np.clip(np.nan_to_num(z), -1.0, 1.0) * 127.0
        ).astype(np.int8)
    raise ValueError(f"unknown codec kind {kind!r}; valid: {quantize.KINDS}")


# jaxlint: disable=precision-discipline (audited fork: numpy twin of
# quantize.decode — raw passes the storage dtype through by design,
# uint8 pixel obs must reach the torso un-floated)
def np_decode(kind: str, stats: dict, q: np.ndarray) -> np.ndarray:
    """Numpy twin of quantize.decode (tests cross-check it against the
    device decode; the trainers only ever decode on device)."""
    if kind == "raw":
        return np.asarray(q)
    if kind == "f16":
        return np.asarray(q, np.float32)
    if kind == "bool8":
        return np.asarray(q, np.float32)
    if kind == "i8_unit":
        return np.asarray(q, np.float32) / 127.0
    if kind == "i8":
        return (np.asarray(q, np.float32) * (stats["scale"] / 127.0)
                + stats["mean"]).astype(np.float32)
    raise ValueError(f"unknown codec kind {kind!r}; valid: {quantize.KINDS}")


def storage_np_dtype(kind: str, dtype) -> np.dtype:
    """Numpy storage dtype for one leaf (mirrors quantize.storage_dtype)."""
    if kind == "raw":
        return np.dtype(dtype)
    if kind == "f16":
        return np.dtype(np.float16)
    if kind in ("i8", "i8_unit", "bool8"):
        return np.dtype(np.int8)
    raise ValueError(f"unknown codec kind {kind!r}; valid: {quantize.KINDS}")
