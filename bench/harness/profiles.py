"""Split profiles built from a configuration file's numbers: FLOPs per layer
and the bits that cross the link at each split point.  The benchmark's own
copies of the program's ``core.profiles`` arithmetic, so the yardstick's
inputs do not move with the program.

A profile is a plain dict of numpy arrays (``layer_flops``, ``out_bits``)
and floats (``input_bits``, ``result_bits``); ``to_program`` wraps it as
the program's ``SplitProfile``.
"""
from __future__ import annotations

import numpy as np


def cnn(spec: dict) -> dict:
    """A conv chain: ``layers`` is a list of ``[cout, k, stride, pool]``.
    FLOPs per layer are 2*oh*ow*cout*cin*k*k plus 4 compares per pooled
    output; the bits leaving a layer are its activations at ``act_bits``;
    the raw input is an 8-bit image."""
    h = w = int(spec["input_hw"])
    c = int(spec["in_channels"])
    flops, out = [], []
    for cout, k, stride, pool in spec["layers"]:
        h, w = h // stride, w // stride
        fl = 2.0 * h * w * cout * c * k * k
        if pool:
            h, w = h // 2, w // 2
            fl += h * w * cout * 4
        c = cout
        flops.append(fl)
        out.append(h * w * cout * spec["act_bits"])
    return {"layer_flops": np.asarray(flops, np.float32),
            "out_bits": np.asarray(out, np.float32),
            "input_bits": float(spec["input_hw"] ** 2 * spec["in_channels"]
                                * 8),
            "result_bits": float(spec["result_bits"])}


def transformer(model: dict, seq: int, act_bits: int = 16) -> dict:
    """A dense decoder of identical attention + gated-FFN blocks, for one
    request of ``seq`` tokens: per block the q/k/v and output projections,
    causal scores and values (half the square), and three FFN matmuls; the
    residual stream crosses the link; the input is ``seq`` 32-bit ids and
    the result one 32-bit token id."""
    d = model["hidden_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    fl = (2.0 * seq * d * (h + 2 * kv) * hd
          + 2.0 * 2.0 * seq * seq * h * hd * 0.5
          + 2.0 * seq * h * hd * d
          + 2.0 * seq * d * model["intermediate_size"] * 3)
    n = model["num_hidden_layers"]
    return {"layer_flops": np.full(n, fl, np.float32),
            "out_bits": np.full(n, seq * d * act_bits, np.float32),
            "input_bits": float(seq * 32.0),
            "result_bits": 32.0}


def build(cfg: dict) -> dict:
    spec = cfg["profile"]
    if spec["kind"] == "cnn":
        return cnn(spec)
    if spec["kind"] == "transformer":
        return transformer(cfg["model"], spec["seq"], spec["act_bits"])
    raise ValueError(f"unknown profile kind {spec['kind']!r}")


def to_program(prof: dict, name: str):
    import jax.numpy as jnp
    from repro.core.profiles import SplitProfile
    return SplitProfile(name=name,
                        layer_flops=jnp.asarray(prof["layer_flops"]),
                        out_bits=jnp.asarray(prof["out_bits"]),
                        input_bits=prof["input_bits"],
                        result_bits=prof["result_bits"])
