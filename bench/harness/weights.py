"""Random weights for a dense GQA decoder configuration, made on the device
in one jitted call from the seed, in the dtype they are served in and laid
out as the program's ``models.transformer`` holds them:

    embed (Vp, d); units: ({norm1 (L, d), mixer {wq (L, d, H, hd),
    wk/wv (L, d, K, hd), wo (L, H, hd, d)}, norm2 (L, d), ffn {w_in (L, d, f),
    w_gate (L, d, f), w_out (L, f, d)}},); tail (); final_norm (d,);
    lm_head (d, Vp)

Matrices are truncated normals (within two standard deviations) scaled by
one over the square root of their fan-in; norm weights are 1 + 0.1 N(0, 1)
so that a norm whose weight is dropped shows.  The benchmark's reference
reads these same arrays: the weights are the benchmark's, not the
program's.
"""
from __future__ import annotations

from functools import partial

import numpy as np


def shapes(model: dict) -> dict:
    """Leaf name -> (shape, fan-in or None for a norm weight)."""
    d, n = model["hidden_size"], model["num_hidden_layers"]
    h, k = model["num_attention_heads"], model["num_key_value_heads"]
    hd, f, vp = d // h, model["intermediate_size"], model["padded_vocab"]
    return {
        "embed": ((vp, d), d),
        "norm1": ((n, d), None),
        "wq": ((n, d, h, hd), d),
        "wk": ((n, d, k, hd), d),
        "wv": ((n, d, k, hd), d),
        "wo": ((n, h, hd, d), h * hd),
        "norm2": ((n, d), None),
        "w_in": ((n, d, f), d),
        "w_gate": ((n, d, f), d),
        "w_out": ((n, f, d), f),
        "final_norm": ((d,), None),
        "lm_head": ((d, vp), d),
    }


def _make(key, model):
    import jax
    import jax.numpy as jnp
    dt = jnp.dtype(model["dtype"])
    out = {}
    for i, (name, (shape, fan_in)) in enumerate(sorted(shapes(model)
                                                       .items())):
        k = jax.random.fold_in(key, i)
        if fan_in is None:
            x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) \
                / np.sqrt(fan_in)
        out[name] = x.astype(dt)
    return out


def make(key, model: dict) -> dict:
    """The flat leaf dict (see ``shapes``)."""
    import jax
    return jax.jit(partial(_make, model=model))(key)


def program_layout(w: dict) -> dict:
    """The flat leaves as the program's parameter tree."""
    unit = {"norm1": w["norm1"],
            "mixer": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"]},
            "norm2": w["norm2"],
            "ffn": {"w_in": w["w_in"], "w_out": w["w_out"],
                    "w_gate": w["w_gate"]}}
    return {"embed": w["embed"], "units": (unit,), "tail": (),
            "final_norm": w["final_norm"], "lm_head": w["lm_head"]}
