"""Small CPU stand-ins for the benchmark's configurations, so the harness
can be driven end to end here without a chip (``core.execute`` with
``check_chip=False``)."""
from __future__ import annotations

import time

from bench.harness import core

SMALL_NET = {"n_users": 12, "n_aps": 3, "n_subchannels": 6,
             "area_m": 200.0, "bandwidth_hz": 40e6}
SMALL_MODEL = {"name": "internlm2-1.8b:tiny", "hidden_size": 256,
               "num_hidden_layers": 2, "num_attention_heads": 4,
               "num_key_value_heads": 2, "intermediate_size": 512,
               "vocab_size": 512, "padded_vocab": 512}


def tiny_config(config_name: str) -> dict:
    cfg = core.load_json(core.BENCH / "configs" / f"{config_name}.json")
    cfg["network"] = dict(cfg["network"], **SMALL_NET)
    if "model" in cfg:
        cfg["model"] = dict(cfg["model"], **SMALL_MODEL)
        cfg["profile"] = dict(cfg["profile"], seq=16)
        cfg["check_requests"] = 4
    return cfg


def run_tiny(cell: str, seed: int = 7, seconds: float = 2.0, trace=False,
             config=None, traffic=None, control=None):
    """One run of ``cell`` at the small size; returns the result object.
    ``control``: the precision of a control run (``core.Run.control``)."""
    bm = core.load_json(core.ROOT / "BENCHMARK.json")
    c = {w["name"]: w for w in bm["workloads"]}[cell]
    cfg = config or tiny_config(c["config"])
    run = core.make_run(["--workload", cell, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace",
                         str(int(trace))], time.monotonic(), cfg)
    if "model" in cfg:
        run.traffic = dict(run.traffic, prompt_len=cfg["profile"]["seq"],
                           decode_steps=4)
    if traffic is not None:
        run.traffic = dict(run.traffic, **traffic)
    run.control = control
    return core.execute(run, check_chip=False)


def step_grad(result: dict) -> dict:
    """The check of the fused step's gradient (``step_grad_p<NN>``) in a
    result object: its value and limit."""
    (check,) = [c for k, c in result["checks"].items()
                if k.startswith("step_grad_p")]
    return check
