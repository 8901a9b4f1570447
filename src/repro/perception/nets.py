"""Egocentric-primitive implementations (Table I) as small JAX models.

These are the *on-device* workloads of the wearable: their compiled FLOP
counts (jax cost_analysis) parameterize the PnPSim taskgraphs, replacing the
paper's proprietary EDA/profiling data with measured numbers from real
implementations:

  * VIO frontend  — TLIO-style IMU 1D-ResNet [arXiv:2007.01867 adjacent,
                    per paper ref 24] + greyscale feature/patch frontend.
  * Hand tracking — UMETrack-style multi-view crop CNN -> 21 keypoints/hand
                    [SIGGRAPH Asia '22, paper ref 20].
  * Eye tracking  — VOG gaze CNN per eye [paper ref 16/21].
  * VAD           — tiny conv/GRU speech detector (paper ref 8).
  * ASR           — streaming Conformer-lite acoustic model + CTC
                    [arXiv:2005.08100, paper ref 19].
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.phases import phase
from ..nn import core


def _conv(key, x, cout, k=3, stride=1, groups=1):
    cin = x.shape[-1]
    w = core.dense_init(key, (k, k, cin // groups, cout), x.dtype,
                        fan_in=k * k * cin // groups)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def _conv1d(key, x, cout, k=3, stride=1):
    cin = x.shape[-1]
    w = core.dense_init(key, (k, cin, cout), x.dtype, fan_in=k * cin)
    return jax.lax.conv_general_dilated(
        x, w, (stride,), "SAME", dimension_numbers=("NWC", "WIO", "NWC"))


# --------------------------------------------------------------------------
# Hand tracking (what am I interacting with?)
# --------------------------------------------------------------------------

def hand_tracker(key, crops):
    """crops: (B, 2 hands, 128, 128, 1) -> keypoints (B, 2, 21, 3)."""
    B = crops.shape[0]
    x = crops.reshape(B * 2, 128, 128, 1)
    ks = jax.random.split(key, 8)
    widths = (16, 32, 64, 96, 128)
    for i, w in enumerate(widths):
        x = jax.nn.relu(_conv(ks[i], x, w, stride=2))
    x = x.mean(axis=(1, 2))
    x = jax.nn.relu(x @ core.dense_init(ks[5], (128, 128), x.dtype))
    kp = x @ core.dense_init(ks[6], (128, 21 * 3), x.dtype)
    return kp.reshape(B, 2, 21, 3)


# --------------------------------------------------------------------------
# Eye tracking (what do I see?)
# --------------------------------------------------------------------------

def eye_tracker(key, eyes):
    """eyes: (B, 2, 96, 96, 1) -> gaze vector + pupil (B, 2, 4)."""
    B = eyes.shape[0]
    x = eyes.reshape(B * 2, 96, 96, 1)
    ks = jax.random.split(key, 6)
    for i, w in enumerate((12, 24, 48, 64)):
        x = jax.nn.relu(_conv(ks[i], x, w, stride=2))
    x = x.mean(axis=(1, 2))
    out = x @ core.dense_init(ks[4], (64, 4), x.dtype)
    return out.reshape(B, 2, 4)


# --------------------------------------------------------------------------
# VIO (where am I?)
# --------------------------------------------------------------------------

def vio_imu_net(key, imu_window):
    """TLIO-style: (B, 200, 6) IMU -> displacement + covariance (B, 6)."""
    x = imu_window
    ks = jax.random.split(key, 8)
    x = jax.nn.relu(_conv1d(ks[0], x, 32, k=7, stride=2))
    for i, w in enumerate((64, 64, 128, 128)):
        h = jax.nn.relu(_conv1d(ks[1 + i], x, w, stride=2 if w != x.shape[-1] else 1))
        x = h
    x = x.mean(axis=1)
    return x @ core.dense_init(ks[6], (128, 6), x.dtype)


def vio_frontend(key, frame):
    """Visual feature frontend per greyscale frame (B, 240, 320, 1)."""
    ks = jax.random.split(key, 5)
    x = frame
    for i, w in enumerate((8, 16, 32)):
        x = jax.nn.relu(_conv(ks[i], x, w, stride=2))
    heat = _conv(ks[3], x, 1)          # corner heatmap
    desc = _conv(ks[4], x, 32)         # descriptors
    return heat, desc


# --------------------------------------------------------------------------
# Audio (what do I say/hear?)
# --------------------------------------------------------------------------

def vad(key, mel):
    """(B, 100, 40) 1s of mel frames -> speech prob."""
    ks = jax.random.split(key, 3)
    x = jax.nn.relu(_conv1d(ks[0], mel, 32, stride=2))
    x = jax.nn.relu(_conv1d(ks[1], x, 32, stride=2))
    x = x.mean(axis=1)
    return jax.nn.sigmoid(x @ core.dense_init(ks[2], (32, 1), x.dtype))


def asr_conformer(key, mel):
    """Streaming Conformer-lite: (B, 100, 80) 1s mel -> CTC logits.

    12 blocks, d=256: conv subsample x4 then (ffn + self-attn + conv) blocks.
    """
    ks = jax.random.split(key, 64)
    x = jax.nn.relu(_conv1d(ks[0], mel, 256, stride=2))
    x = jax.nn.relu(_conv1d(ks[1], x, 256, stride=2))   # (B, 25, 256)
    d, heads = 256, 4
    ki = 2
    for blk in range(12):
        # half-FFN
        h = jax.nn.silu(x @ core.dense_init(ks[ki], (d, 4 * d), x.dtype))
        x = x + 0.5 * (h @ core.dense_init(ks[ki + 1], (4 * d, d), x.dtype,
                                           fan_in=4 * d))
        # self-attention (short streaming window -> direct sdpa)
        q = (x @ core.dense_init(ks[ki + 2], (d, d), x.dtype)).reshape(
            x.shape[0], -1, heads, d // heads)
        k_ = (x @ core.dense_init(ks[ki + 3], (d, d), x.dtype)).reshape(
            x.shape[0], -1, heads, d // heads)
        v = (x @ core.dense_init(ks[ki + 4], (d, d), x.dtype)).reshape(
            x.shape[0], -1, heads, d // heads)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_) / jnp.sqrt(d / heads)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + o.reshape(x.shape[0], -1, d)
        # depthwise conv module
        x = x + jax.nn.silu(_conv1d(ks[ki + 5], x, d, k=9))
        ki += 5
    return x @ core.dense_init(ks[-1], (d, 1024), x.dtype)


# --------------------------------------------------------------------------
# measured FLOPs per invocation
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def measured_flops() -> dict[str, float]:
    """Compiled-FLOPs per single invocation of each primitive net.

    Always derived on the host CPU backend, whatever accelerator the
    process holds: the wearable model must not depend on the device
    that runs the simulator (a TPU compile counts 12-61% more FLOPs
    for the same nets), and `core/calibrated.json` was fit against the
    CPU counts."""
    cpu = jax.devices("cpu")[0]
    on_cpu = jax.sharding.SingleDeviceSharding(cpu)
    with jax.default_device(cpu):
        key = jax.random.PRNGKey(0)

    def flops(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=on_cpu)
                for s in shapes]
        c = jax.jit(lambda *a: fn(key, *a)).lower(*args).compile()
        return float(c.cost_analysis()["flops"])

    with phase("nets.measured_flops"):
        return {
            "hand_tracker": flops(hand_tracker, (1, 2, 128, 128, 1)),
            "eye_tracker": flops(eye_tracker, (1, 2, 96, 96, 1)),
            "vio_imu": flops(vio_imu_net, (1, 200, 6)),
            "vio_frontend": flops(vio_frontend, (1, 240, 320, 1)),
            "vad": flops(vad, (1, 100, 40)),
            "asr_1s": flops(asr_conformer, (1, 100, 80)),
        }
