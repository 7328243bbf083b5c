"""Platform presets: one place that pins the execution environment a
benchmark ran under, so two BENCH_*.json files are comparable or visibly
not.

The problem this solves: XLA flags and host-device-count env vars silently
change benchmark numbers (latency-hiding scheduler, forced CPU device
count, allocator), but they live in whoever's shell launched the process —
a Makefile target, a CI runner, a developer tmux.  Two runs of the same
benchmark with different ambient env produce different numbers that look
like regressions.  A preset names the intended environment, ``apply()``
pins it (env vars must be set before jax initialises), and ``describe()``
reports what was EFFECTIVE at run time — benchmarks/run.py embeds that
into every BENCH_*.json config block.

Presets (names are the contract; the flag sets are the current best
known-good for this repo's workloads):

  cpu        single-process CPU, no forced device count — the tier-1 test
             environment.
  cpu-mesh   CPU with ``--xla_force_host_platform_device_count=4`` — what
             `make test-solver` uses to exercise shard_map paths; REQUIRED
             for the sharded-backend benchmarks to mean anything on a
             one-socket machine.
  gpu        the standard latency-hiding flag set (triton softmax fusion,
             async collectives, latency-hiding scheduler).
  tpu        no XLA flag overrides — Mosaic/XLA:TPU defaults; kernels in
             kernels/ take over the hot loops.

Allocator note (run.sh-style, can't be set from inside the process):
benchmarks on glibc malloc see up to ~10% jitter from arena contention on
many-core hosts; preload tcmalloc when available:
  LD_PRELOAD=/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4
``describe()`` records whether a preload was active so runs are comparable.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, Optional

_FORCE_DEVICES = "--xla_force_host_platform_device_count"

_GPU_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true "
    "--xla_gpu_triton_gemm_any=True "
    "--xla_gpu_enable_async_collectives=true "
    "--xla_gpu_enable_latency_hiding_scheduler=true "
    "--xla_gpu_enable_highest_priority_async_stream=true"
)


@dataclass(frozen=True)
class Preset:
    name: str
    platform: Optional[str] = None      # jax_platform_name, None = leave
    xla_flags: str = ""                 # appended to ambient XLA_FLAGS
    host_devices: Optional[int] = None  # forced CPU device count
    env: Dict[str, str] = field(default_factory=dict)


PRESETS = {
    "cpu": Preset("cpu", platform="cpu"),
    "cpu-mesh": Preset("cpu-mesh", platform="cpu", host_devices=4),
    "gpu": Preset("gpu", platform="gpu", xla_flags=_GPU_FLAGS),
    "tpu": Preset("tpu", platform="tpu"),
}

# the preset apply() pinned this process to (None = never applied: the
# ambient environment is whatever the launcher exported)
_ACTIVE: Optional[str] = None


def set_platform(platform: str) -> None:
    """Pin the jax platform ('cpu'|'gpu'|'tpu').  Only effective before
    jax initialises its backends — call at process start."""
    import jax
    jax.config.update("jax_platform_name", platform)


def set_host_device_count(n: int) -> None:
    """Force the CPU backend to expose ``n`` devices (shard_map testing on
    one-socket machines).  Appends to XLA_FLAGS, replacing any previous
    forced count; must run before jax initialises."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith(_FORCE_DEVICES)]
    flags.append(f"{_FORCE_DEVICES}={int(n)}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def apply(name: str) -> Preset:
    """Apply a named preset to this process.  Idempotent; raises on an
    unknown name.  Returns the preset for logging."""
    global _ACTIVE
    preset = PRESETS.get(name)
    if preset is None:
        raise ValueError(
            f"unknown platform preset {name!r}; have {sorted(PRESETS)}")
    if preset.xla_flags:
        ambient = os.environ.get("XLA_FLAGS", "")
        if preset.xla_flags not in ambient:
            os.environ["XLA_FLAGS"] = (ambient + " " + preset.xla_flags).strip()
    if preset.host_devices is not None:
        set_host_device_count(preset.host_devices)
    for k, v in preset.env.items():
        os.environ.setdefault(k, v)
    if preset.platform is not None:
        set_platform(preset.platform)
    _ACTIVE = name
    return preset


def active_preset() -> Optional[str]:
    return _ACTIVE


def describe() -> Dict:
    """The EFFECTIVE environment of this process, for benchmark config
    blocks: what jax actually sees, not what a preset intended.  Safe to
    call whether or not ``apply()`` ever ran."""
    import jax
    devices = jax.devices()
    forced = None
    for f in os.environ.get("XLA_FLAGS", "").split():
        if f.startswith(_FORCE_DEVICES + "="):
            try:
                forced = int(f.split("=", 1)[1])
            except ValueError:
                forced = None
    return {
        "preset": _ACTIVE or "ambient",
        "platform": devices[0].platform,
        "n_devices": len(devices),
        "forced_host_devices": forced,
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "ld_preload": os.environ.get("LD_PRELOAD", ""),
        "jax_enable_x64": bool(jax.config.read("jax_enable_x64")),
    }


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` — a fixed path, since the path is part of
    what a later process must find again.  Entry points call this from
    ``main``; importing this module sets nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "mem_bw": 819e9},
}


def roofline_peaks(device_kind: Optional[str] = None) -> Dict[str, float]:
    """Peak FLOP/s and memory bandwidth of ``device_kind`` (default: the
    first visible device's) for roofline ratios.  A kind missing from
    ``PEAKS`` is an error: a roofline against another chip's peaks — or
    against a guess for a CPU — would be a number with no meaning."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    peaks = PEAKS.get(device_kind)
    if peaks is None:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; have {sorted(PEAKS)}")
    return dict(peaks, basis=device_kind)
