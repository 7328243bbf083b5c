"""FLOPs a dense GQA decoder needs to serve a request, from the
configuration's shapes alone (2 per multiply-add):

* per token, every layer's q, k, v and output projections and the three
  gated-FFN matmuls: 2 * (d*H*hd + 2*d*K*hd + H*hd*d + 3*d*f);
* per token at context c (the keys it attends to, itself included),
  scores and values: 4 * c * H * hd per layer;
* the LM head, 2 * d * V, once per token whose next token is wanted: the
  last prompt position and every decoded token.

The vocabulary is the published one; rows that only pad the table do not
count.  A prompt of ``s`` tokens with ``n`` served tokens needs the prompt
processed once (causal context 1..s), then n - 1 decode steps at context
s+1 .. s+n-1, with the head at s positions in all.
"""
from __future__ import annotations


def _layer_matmul_flops(model: dict) -> float:
    d = model["hidden_size"]
    h, k = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    f = model["intermediate_size"]
    return 2.0 * (d * h * hd + 2 * d * k * hd + h * hd * d + 3 * d * f)


def request_flops(model: dict, prompt: int, served: int) -> float:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    hd = d // h
    n_layers = model["num_hidden_layers"]
    tokens = prompt + served - 1
    ctx_sum = tokens * (tokens + 1) / 2.0       # contexts 1 .. tokens
    return (n_layers * (_layer_matmul_flops(model) * tokens
                        + 4.0 * hd * h * ctx_sum)
            + 2.0 * d * model["vocab_size"] * served)
