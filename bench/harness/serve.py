"""Driver for served-model configurations: closed-loop serving rounds of a
split model through ``SplitInferenceCluster``.

Per round, back to back: every user of every cell re-posts its QoE
deadline with the mix's probability (``submit``), ``cluster.step()`` runs
the admission solve, and ``cluster.serve_round`` serves one prompt per
user on the installed schedules: each split group's device prefix and
edge suffix, then the greedy decode continuation.  Every request of a
round is due at the round's start.

End-to-end metrics:

* ``ttft_p95_ms`` — over every request of the window, from the round's
  start to its split group's edge logits being ready on the device;
* ``tok_s`` — output tokens of the window's rounds (the first token and
  the decoded ones) over the span of those rounds.

Correctness: after the window, a sample of the window's requests drawn
from the seed is run through the plain reference forward
(``bench/reference/internlm2.py``) over each prompt and its served tokens;
the widest gap by which a served token's reference logit lies below the
reference's best at that position is compared, for the split path's first
token and for every decoded token.  The admission solves of the first
rounds are replayed, and the fused GD step compared, by the ERA reference
as in ``bench.harness.solver``.

The first-token time is stamped by the benchmark's wrappers around the two
module functions of ``repro.serving.split_runtime`` that the engine calls
per split group; the edge wrapper waits for the group's logits, which the
engine itself reads back to the host at once, and takes their greedy token
at the last prompt position, as the engine does.  A program whose split
path no longer calls those two functions leaves the wrappers nothing to
stamp: its runs fail for want of first-token times.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench.harness import core, profiles, solver, traffic as tr, weights


def _model_cfg(model: dict):
    """The program's model configuration with the file's RMSNorm epsilon
    (the published one; the program's own file states another), checked
    against the file's other numbers."""
    from repro.configs import get_config
    cfg = get_config(model["name"]).replace(norm_eps=model["rms_norm_eps"])
    want = {"n_layers": model["num_hidden_layers"],
            "d_model": model["hidden_size"],
            "n_heads": model["num_attention_heads"],
            "n_kv_heads": model["num_key_value_heads"],
            "d_ff": model["intermediate_size"],
            "vocab_size": model["vocab_size"],
            "padded_vocab": model["padded_vocab"],
            "rope_theta": model["rope_theta"],
            "norm_eps": model["rms_norm_eps"], "dtype": model["dtype"]}
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise core.BenchError(f"the program's {model['name']} differs from "
                              f"the configuration file: {got} vs {want}")
    return cfg


class Capture:
    """Wrappers around the engine's calls into ``split_runtime``: the
    device-prefix call records the group's prompts, the edge-suffix call
    waits for its logits, stamps when they were ready and keeps their
    greedy token at the last prompt position."""

    def __init__(self, spans: core.Spans):
        self.spans = spans
        self.events: List[tuple] = []      # (t_ready, prompts, first tok)
        self._pending: List[np.ndarray] = []

    def install(self):
        import jax
        import jax.numpy as jnp
        from repro.serving import split_runtime as sr
        real_dev, real_edge = sr.device_forward, sr.edge_forward
        spans = self.spans

        def device_forward(params, cfg, tokens, split, **kw):
            self._pending.append(np.asarray(tokens))
            t0 = time.monotonic()
            with spans.span("split.device_forward"):
                out = real_dev(params, cfg, tokens, split, **kw)
                if spans.traced:
                    jax.block_until_ready(out)
                    spans.record("split", t0, time.monotonic())
            return out

        def edge_forward(params, cfg, x, positions, split, **kw):
            t0 = time.monotonic()
            with spans.span("split.edge_forward"):
                logits = real_edge(params, cfg, x, positions, split, **kw)
                jax.block_until_ready(logits)
            t1 = time.monotonic()
            spans.record("split", t0, t1)
            first = jnp.argmax(logits[:, -1], -1)
            self.events.append((t1, self._pending.pop(), first))
            return logits

        sr.device_forward, sr.edge_forward = device_forward, edge_forward
        undo = [lambda: setattr(sr, "device_forward", real_dev),
                lambda: setattr(sr, "edge_forward", real_edge)]
        if spans.traced:
            from repro.models import transformer as T
            undo += [spans.wrap(T, "prefill", "decode.prefill", timed=True),
                     spans.wrap(T, "decode_step", "decode.step",
                                timed=True)]
        return undo


def run(run: core.Run) -> core.Outcome:
    import jax
    from repro.core.era import Weights
    from repro.serving.cluster import SplitInferenceCluster
    from repro.telemetry import TelemetryBus

    cfg, mix = run.config, run.traffic
    model = cfg["model"]
    mcfg = _model_cfg(model)
    net = solver._net(cfg)
    prof = profiles.build(cfg)
    n_cells, n_users = int(cfg["cells"]), net.n_users
    seq, steps = int(mix["prompt_len"]), int(mix["decode_steps"])
    if seq != cfg["profile"]["seq"]:
        raise core.BenchError("the mix's prompt length differs from the "
                              "profile's")
    w = weights.make(tr.jax_key(run.seed, 4), model)
    params = weights.program_layout(w)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    chains = tr.ChannelChains(run.seed, net, n_cells, 1, 0.0)
    bus = TelemetryBus(clock=time.monotonic, capacity=1 << 16)
    program_prof = profiles.to_program(prof, cfg["profile"]["name"])
    sweights = Weights(**cfg["weights"])
    cluster = SplitInferenceCluster(
        params, mcfg, program_prof, spec=solver._spec(cfg), weights=sweights,
        drift_threshold=cfg["drift_threshold"], clock=time.monotonic,
        bus=bus)
    ids = [cluster.add_cell(chains.scenario(b, 0), q0=mix["q0_s"])
           for b in range(n_cells)]
    rng = tr.np_rng(run.seed, 5)
    log = solver.Log(q0=float(mix["q0_s"]),
                     drift_threshold=float(cfg["drift_threshold"]),
                     net=dict(cfg["network"]), prof=prof, chains=chains,
                     check_lanes=list(range(n_cells)))
    log.program_prof, log.weights = program_prof, sweights
    n_replay = int(mix["warmup_rounds"]) + int(mix["check_rounds"])
    jax.block_until_ready(params)
    run.say(f"spec: {solver._spec(cfg)}")
    run.say(f"model {model['name']}: layers={mcfg.n_layers} "
            f"d_model={mcfg.d_model} heads={mcfg.n_heads}/{mcfg.n_kv_heads}"
            f" d_ff={mcfg.d_ff} vocab={mcfg.vocab_size} dtype={mcfg.dtype} "
            f"params={n_params}; cells={n_cells} users={n_users} "
            f"M={net.n_subchannels} prompt={seq} decode_steps={steps}")

    cluster.start(threaded=False)
    log.boot_outcomes = {b: cluster.last_outcome(ids[b])
                         for b in range(n_cells)}
    cap = Capture(run.spans)
    undo = cap.install()
    served: List[Dict] = []

    def one_round(force=None):
        t0 = time.monotonic()
        reposts, prompts = tr.chat_round(
            rng, n_cells, n_users, mix["repost_prob"], mix["deadline_s"],
            seq, model["vocab_size"])
        if force is not None:
            reposts = [p for p in reposts if p[0] in force] + [
                (c, 0, float(mix["deadline_s"][0])) for c in force]
        with run.spans.span("submit"):
            for c, u, q in reposts:
                cluster.submit(ids[c], u, q)
        with run.spans.span("cluster.step"):
            rnd = cluster.step()
        t_solved = time.monotonic()
        n_ev = len(cap.events)
        with run.spans.span("cluster.serve_round"):
            out = cluster.serve_round({ids[c]: prompts[c]
                                       for c in range(n_cells)},
                                      decode_steps=steps)
        t1 = time.monotonic()
        rec = solver.Round(t0, t1, 0, [(t0, c, u, q) for c, u, q in reposts])
        if rnd is not None:
            rec.cells, rec.t_installed = tuple(rnd.cells), rnd.t_installed
        if len(log.rounds) < n_replay:
            rec.outcomes = {b: cluster.last_outcome(ids[b])
                            for b in range(n_cells)}
        log.rounds.append(rec)
        served.append({"t_solved": t_solved,
                       "prompts": prompts, "edge": cap.events[n_ev:],
                       "tokens": np.stack([np.stack(
                           [r.tokens_out for r in out[ids[c]]])
                           for c in range(n_cells)])})

    try:
        one_round(force=range(n_cells))      # every lane bucket and the
        one_round(force=[0])                 # serve path, warmed in set-up
        first = len(log.rounds)
        with run.window():
            while time.monotonic() - run.t_window0 < run.seconds:
                one_round()
        log.window = slice(first, len(log.rounds))
        run.end_trace()
        while len(log.rounds) < n_replay:
            one_round()
    finally:
        for u in undo:
            u()
        cluster.stop(drain=False)
    log.n_check = n_replay
    rounds = log.rounds[log.window]
    win = served[log.window]
    t_end = rounds[-1].t_end
    ttft = [t_ready - r.t_start for r, s in zip(rounds, win)
            for t_ready, toks, _ in s["edge"] for _ in range(len(toks))]
    tokens = sum(int(s["tokens"].size) for s in win)
    n_req = sum(int(s["tokens"].shape[0] * s["tokens"].shape[1])
                for s in win)
    e2e = {"ttft_p95_ms": 1e3 * tr.percentile(ttft, 95),
           "tok_s": tokens / (t_end - run.t_window0)}
    events = [e.fields for e in bus.snapshot("admission_round")
              if run.t_window0 <= e.t <= t_end]
    n_comp, _, n_hit = run.compiles_in_window
    run.say(f"window: rounds={len(rounds)} requests={n_req} "
            f"tokens={tokens} ttft_p50_ms={1e3 * tr.percentile(ttft, 50)!r}"
            f" ttft_p95_ms={e2e['ttft_p95_ms']!r} tok_s={e2e['tok_s']!r} "
            f"solves={len(events)}")
    run.say(f"compiles in window: requests={n_comp} persistent-cache hits="
            f"{n_hit}; set-up compile s={run.setup_compile_s!r}")
    run.say("rounds ms (solve, each split group's first token, round): "
            + str([(round(1e3 * (s["t_solved"] - r.t_start), 1),
                    [round(1e3 * (t - r.t_start), 1) for t, _, _ in s["edge"]],
                    round(1e3 * (r.t_end - r.t_start), 1))
                   for r, s in zip(rounds, win)]))
    solver.say_operating_point(
        run, [cluster.qoe_attainment(i) for i in ids],
        [np.asarray(cluster.installed_schedule(i).pred_latency) for i in ids],
        [np.bincount(cluster.installed_schedule(i).split,
                     minlength=mcfg.n_layers + 1) for i in ids])

    # ---- correctness: free the program's state, then the references
    memory = core.device_info(run.cell["chips"])
    del cluster, params
    numbers = solver.check_numbers(run, log)
    numbers["logit_gap"] = logit_gap(w, model, win, run.seed,
                                     int(cfg["check_requests"]),
                                     quantized=run.control is not None)
    limits = cfg["limits"]
    checks = {k: (numbers[k], limits[k]) for k in limits}
    readings = {"rounds": rounds, "events": events, "t_end": t_end,
                "requests": n_req, "tokens": tokens, "model": model,
                "prompt": seq, "decode_steps": steps, "log": log,
                "weights": w, "served": win}
    return core.Outcome(e2e=e2e, readings=readings, checks=checks,
                        attempted=n_req, failed=n_req - len(ttft),
                        device=memory)


def sample_requests(win, seed: int, n: int):
    """``n`` window requests drawn from the seed: (round, cell, user)."""
    all_req = [(i, c, u) for i, s in enumerate(win)
               for c in range(s["tokens"].shape[0])
               for u in range(s["tokens"].shape[1])]
    rng = tr.np_rng(seed, 6)
    pick = rng.choice(len(all_req), min(n, len(all_req)), replace=False)
    return [all_req[k] for k in sorted(pick)]


def request_rows(win, picks):
    """Per sampled request: the prompt followed by all but the last served
    token (the reference's input), the served tokens, and the split path's
    first token."""
    rows, served, first = [], [], []
    for i, c, u in picks:
        s = win[i]
        prompt, toks = s["prompts"][c, u], s["tokens"][c, u]
        rows.append(np.concatenate([prompt, toks[:-1]]))
        served.append(toks)
        split_first = None
        for _, group, tok in s["edge"]:
            hit = np.nonzero((group == prompt).all(axis=1))[0]
            if hit.size:
                split_first = int(np.asarray(tok)[hit[0]])
        first.append(split_first)
    return np.stack(rows), np.stack(served), first


def gaps(ref_logits, tokens) -> np.ndarray:
    """Per position, how far the reference's logit of ``tokens`` lies below
    its best logit there."""
    ref = np.asarray(ref_logits, np.float64)
    best = ref.max(axis=-1)
    got = np.take_along_axis(ref, np.asarray(tokens)[..., None], -1)[..., 0]
    return best - got


def logit_gap(w, model, win, seed: int, n: int,
              quantized: bool = False) -> float:
    """The widest gap over the sampled requests' served tokens (and the
    split path's first tokens).  ``quantized``: the control, whose own
    greedy token at each position is read instead of the served one."""
    from bench.reference import internlm2 as ref
    picks = sample_requests(win, seed, n)
    rows, served, first = request_rows(win, picks)
    seq = rows.shape[1] - served.shape[1] + 1
    want = ref.logits(w, model, rows, seq - 1)
    if not np.isfinite(want).all():
        raise core.BenchError("the reference's logits are not finite")
    if quantized:
        ctl = ref.logits(w, model, rows, seq - 1, quantized=True)
        return float(np.max(gaps(want, ctl.argmax(-1))))
    g = gaps(want, served)
    worst = float(np.max(g))
    for k, tok in enumerate(first):
        if tok is None:
            return float("inf")
        worst = max(worst, float(gaps(want[k, 0], np.asarray(tok))))
    return worst
