#!/usr/bin/env python3
"""Bring-up smoke of the ERA solver and the split-serving path on a TPU.

Run from the repository root, on a machine with a TPU:

    python3 chip_smoke.py                # one chip: phases a-d below
    python3 chip_smoke.py --four-chips   # four chips: the sharded solve
                                         # against the one-chip reference

One process drives every phase through the program's own entry points
and prints each phase's wall and compile seconds on a line of its own:

  a  device check — the first device must be a TPU; there is no CPU
     fallback;
  b  the Li-GD solver at paper scale (U=1250, M=250, N=5) with the
     gemma-2b split profile and ``SolverSpec(step_impl='fused')``: one
     compiled fused step against ``era_step_ref`` at the same block size,
     a bounded ``solve_batch``, and a whole solve at test scale against
     ``step_impl='xla'``;
  c  split serving of gemma-2b at its published widths (18 layers,
     d_model 2048, vocab 256000, bf16, random weights from ``--seed``)
     through cluster → admission → scheduler → engine: 2 cells of 16
     users, 3 admission rounds in sync mode, each served with
     ``decode_steps=4``, then one more serve with every user moved to
     the interior cut F/2; every split group's edge logits are checked
     against the full-model forward;
  d  the last line: ``{"ok": true, "device": {...}}``.

Precision: every contraction of the solver pins ``Precision.HIGHEST`` —
the kernel's SIC matvecs, ``era_step_ref``'s einsums and core.noma's —
so both sides of each solver comparison compute in f32 on the MXU
rather than TPU's default one-pass bf16.  The model runs in bf16 on both
sides of its comparison.

Any failed check exits non-zero and prints no result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# tolerances of the comparisons (see module docstring for precisions)
STEP_GAMMA_RTOL = 1e-4      # kernel vs era_step_ref: Γ
STEP_GRAD_ATOL = 1e-3       #   gradient leaves, scaled by max |ref leaf|
SOLVE_GAMMA_RTOL = 1e-4     # fused vs xla solve: Γ by layer
SOLVE_ALLOC_ATOL = 1e-3     #   allocation leaves, scaled by max |xla leaf|
SHARD_RTOL = 1e-5           # sharded vs reference backend: Γ by layer
SHARD_ALLOC_ATOL = 1e-5     #   allocation leaves, scaled
LOGIT_REL = 2.0 ** -5       # split vs full logits: max |diff| / max |full|
SEQ = 32                    # prompt length of the served model and profile
MAX_STEPS = 40              # GD step bound of every solve: phase b stays
                            # at a few minutes
N_CELLS, USERS, SUBCHANNELS = 2, 16, 8     # phase c's cluster
ROUNDS, DECODE_STEPS = 3, 4                # phase c's admission rounds
SHARDED_CELLS = 16                         # --four-chips batch


class Clock:
    """Wall time of one phase, and the part of it XLA spent compiling
    (backend compile events from jax.monitoring; tracing and lowering,
    which nest, are not counted)."""

    _compile_s = 0.0
    _EVENT = "/jax/core/compile/backend_compile_duration"

    @classmethod
    def install(cls):
        import jax

        def listen(event, duration, **_):
            if event == cls._EVENT:
                cls._compile_s += duration
        jax.monitoring.register_event_duration_secs_listener(listen)

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = Clock._compile_s
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self.t0
        comp = Clock._compile_s - self.c0
        print(f"phase {self.name}: wall_s={wall!r} compile_s={comp!r}"
              f"{'' if exc[0] is None else ' FAILED'}", flush=True)


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def _scaled_err(got, want):
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def jax_key(seed):
    import jax
    return jax.random.PRNGKey(seed)


def _random_alloc(key, u, m):
    """A feasible interior allocation (tests/test_era_step.py's)."""
    import jax
    from repro.core import era
    ks = jax.random.split(key, 5)
    return era.Allocation(
        beta_up=jax.nn.softmax(jax.random.normal(ks[0], (u, m)), axis=1),
        beta_dn=jax.nn.softmax(jax.random.normal(ks[1], (u, m)), axis=1),
        p=jax.numpy.exp(jax.random.normal(ks[2], (u,)) * 0.3) * 0.1,
        p_ap=jax.numpy.exp(jax.random.normal(ks[3], (u,)) * 0.3),
        r=1.0 + jax.numpy.exp(jax.random.normal(ks[4], (u,)) * 0.2))


# ------------------------------------------------------------------ phases
def phase_device(n_chips):
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    check(dev.platform == "tpu",
          f"no TPU: the first device is {dev.platform!r}")
    check(len(devices) == n_chips,
          f"need {n_chips} chip(s), JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def check_fused_step(cfg, prof, seed, block_sizes):
    """One compiled fused step against the oracle at the same block."""
    import jax
    import jax.numpy as jnp
    from repro.core.era import Weights
    from repro.kernels.era_step import ops as eops
    from repro.kernels.era_step.kernel import era_step_fused
    from repro.kernels.era_step.ref import era_step_ref
    from repro.core import network

    scn = network.make_scenario(jax_key(seed), cfg)
    u, m = cfg.n_users, cfg.n_subchannels
    alloc = _random_alloc(jax_key(100 + seed), u, m)
    s_vec = jnp.full((u,), prof.n_layers // 2, jnp.int32)
    operands = eops._operands(scn, prof, s_vec, jnp.full((u,), 0.4), alloc,
                              eops.build_aux(scn), Weights())
    ref = jax.jit(era_step_ref, static_argnames="block_m")
    for bm in block_sizes:
        hlo = era_step_fused.lower(*operands, block_m=bm,
                                   interpret=False).as_text()
        check("tpu_custom_call" in hlo,
              f"bm={bm}: the fused step did not lower to a Pallas kernel")
        g_k, *grads_k = era_step_fused(*operands, block_m=bm,
                                       interpret=False)
        g_r, grads_r = ref(*operands, block_m=bm)
        g_k, g_r = float(g_k[0, 0]), float(g_r)
        check(math.isfinite(g_k) and math.isfinite(g_r),
              f"bm={bm}: non-finite Γ {g_k} / {g_r}")
        g_err = abs(g_k - g_r) / abs(g_r)
        leaf_err = max(_scaled_err(a, b) for a, b in zip(grads_k, grads_r))
        print(f"  step u{u}m{m} bm={bm}: kernel Γ={g_k!r} ref Γ={g_r!r} "
              f"Γ rel err={g_err!r} max scaled grad err={leaf_err!r}",
              flush=True)
        check(g_err <= STEP_GAMMA_RTOL, f"bm={bm}: Γ rel err {g_err}")
        check(leaf_err <= STEP_GRAD_ATOL, f"bm={bm}: grad err {leaf_err}")


def check_solve_vs_xla(seed):
    """A whole solve at test scale: fused kernel step against autodiff."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ligd, network, profiles
    from repro.core.era import Weights

    cfg = network.small_config(n_users=12, n_subchannels=6)
    scn = network.make_scenario(jax_key(3 + seed), cfg)
    prof = profiles.get_profile("nin")
    q = jnp.full((cfg.n_users,), 0.4)
    sx = ligd.SolverSpec(tol=0.0, max_steps=40)
    ox = ligd.solve(scn, prof, q, Weights(), spec=sx)
    of = ligd.solve(scn, prof, q, Weights(),
                    spec=sx.replace(step_impl="fused"))
    g_err = float(np.max(np.abs(of.gamma_by_layer - ox.gamma_by_layer)
                         / np.abs(ox.gamma_by_layer)))
    a_err = max(_scaled_err(getattr(of.alloc, k), getattr(ox.alloc, k))
                for k in ox.alloc._fields)
    same_s = bool(np.array_equal(np.asarray(of.s), np.asarray(ox.s)))
    print(f"  solve u12m6 fused vs xla: same splits={same_s} "
          f"Γ rel err={g_err!r} max scaled alloc err={a_err!r}", flush=True)
    check(same_s, "fused and xla solves chose different splits")
    check(g_err <= SOLVE_GAMMA_RTOL, f"solve Γ rel err {g_err}")
    check(a_err <= SOLVE_ALLOC_ATOL, f"solve alloc err {a_err}")


def phase_solver(spec, seed):
    """Phase b: the fused step and a bounded solve at paper scale."""
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core import ligd, network, profiles
    from repro.core.era import Weights
    from repro.kernels.era_step.kernel import choose_block_m

    cfg = network.NetworkConfig()
    prof = profiles.transformer_profile(get_config("gemma-2b"), seq=SEQ)
    u, m = cfg.n_users, cfg.n_subchannels
    bm = choose_block_m(m, u, cfg.n_aps)
    print(f"  paper scale: U={u} M={m} N={cfg.n_aps} F={prof.n_layers} "
          f"auto block_m={bm} max_steps={spec.max_steps}", flush=True)
    # the program's pick, and a zero-padded grid of legal tiles
    check_fused_step(cfg, prof, seed, (bm, 64))

    scn = network.make_scenario(jax_key(seed), cfg)
    out, = ligd.solve_batch([scn], prof, jnp.full((1, u), 0.4), Weights(),
                            spec=spec)
    leaves_finite = all(np.all(np.isfinite(np.asarray(x)))
                        for x in out.alloc)
    print(f"  solve_batch B=1: total GD steps={out.total_iters} "
          f"split counts={np.bincount(out.s, minlength=prof.n_layers + 1)}"
          f" Γ range=[{float(np.min(out.gamma_by_layer))!r}, "
          f"{float(np.max(out.gamma_by_layer))!r}]", flush=True)
    check(np.all(np.isfinite(out.gamma_by_layer)), "non-finite Γ by layer")
    check(leaves_finite, "non-finite allocation")
    check(np.all((out.s >= 0) & (out.s <= prof.n_layers)),
          "split outside [0, F]")
    check_solve_vs_xla(seed)


def phase_serving(spec, seed):
    """Phase c: gemma-2b split serving through the cluster facade."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core import network, profiles
    from repro.models import transformer as T
    from repro.serving import split_runtime
    from repro.serving.cluster import SplitInferenceCluster

    cfg = get_config("gemma-2b")
    params = jax.jit(T.init, static_argnums=1)(jax_key(seed), cfg)
    n_params = T.param_count(params)
    print(f"  model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} params={n_params}",
          flush=True)
    prof = profiles.transformer_profile(cfg, seq=SEQ)
    ncfg = network.small_config(n_users=USERS, n_subchannels=SUBCHANNELS)
    cluster = SplitInferenceCluster(params, cfg, prof, spec=spec)
    ids = [cluster.add_cell(network.make_scenario(jax_key(seed + 1 + i),
                                                  ncfg), q0=0.4)
           for i in range(N_CELLS)]
    cluster.start(threaded=False)

    full_forward = jax.jit(lambda p, t: T.forward(p, cfg, t)[0])
    stats = []
    pending = []

    def device_forward(params, cfg, tokens, split, **kw):
        pending.append((np.asarray(tokens), split))
        return real_device(params, cfg, tokens, split, **kw)

    def edge_forward(params, cfg, x, positions, split, **kw):
        logits = real_edge(params, cfg, x, positions, split, **kw)
        toks, dev_split = pending.pop()
        check(dev_split == split, "device and edge sides disagree on split")
        n = toks.shape[0]
        # pad to one batch shape so the reference compiles once
        padded = np.concatenate([toks, np.repeat(toks[:1], USERS - n, 0)])
        ref = full_forward(params, jnp.asarray(padded))[:n]
        diff = float(jnp.max(jnp.abs(logits - ref)))
        scale = float(jnp.max(jnp.abs(ref)))
        top2 = jax.lax.top_k(ref[:, -1], 2)[0]
        gap = np.asarray(top2[:, 0] - top2[:, 1])
        agree = np.asarray(jnp.argmax(logits[:, -1], -1)
                           == jnp.argmax(ref[:, -1], -1))
        stats.append((split, n, diff, scale, agree, gap))
        return logits

    real_device = split_runtime.device_forward
    real_edge = split_runtime.edge_forward
    split_runtime.device_forward = device_forward
    split_runtime.edge_forward = edge_forward
    def serve_and_check(label, tokens):
        stats.clear()
        served = cluster.serve_round(tokens, decode_steps=DECODE_STEPS)
        check(not cluster.errors, f"admission errors: {list(cluster.errors)}")
        for cid in ids:
            res = served[cid]
            check(len(res) == USERS, f"cell {cid}: {len(res)} results")
            check(all(x.tokens_out.shape == (DECODE_STEPS,) for x in res),
                  "decode length")
            check(all(np.isfinite(x.latency_s) for x in res),
                  "non-finite latency")
        check(stats, f"{label}: no split group was served")
        for split, n, diff, scale, agree, gap in stats:
            # a flipped argmax counts only where the reference's top two
            # logits are further apart than the two paths differ
            flips = int(np.sum(~agree & (gap > diff)))
            ties = int(np.sum(~agree & (gap <= diff)))
            print(f"  {label} split={split} users={n}: max|diff|={diff!r} "
                  f"max|logit|={scale!r} rel={diff / scale!r} argmax "
                  f"agree={int(agree.sum())}/{n} near-ties={ties}",
                  flush=True)
            check(diff <= LOGIT_REL * scale,
                  f"{label} split {split}: logits differ by {diff}")
            check(flips == 0, f"{label} split {split}: {flips} argmax flips")

    rng = np.random.default_rng(seed)
    try:
        for r in range(ROUNDS):
            for cid in ids:
                for user in rng.choice(USERS, 3, replace=False):
                    cluster.submit(cid, int(user),
                                   float(rng.uniform(0.2, 0.6)))
            rnd = cluster.step()           # sync mode: a failed round raises
            check(rnd is not None, f"round {r}: nothing was solved")
            tokens = {cid: rng.integers(0, cfg.vocab_size, (USERS, SEQ),
                                        dtype=np.int32) for cid in ids}
            serve_and_check(f"round {r}", tokens)
            print(f"  round {r}: version={cluster.schedule_version} "
                  f"cells solved={rnd.cells} attainment="
                  f"{[round(cluster.qoe_attainment(c), 4) for c in ids]}",
                  flush=True)
        # ERA may put every user at one end of the model; move them all to
        # an interior cut through the engine's schedule swap, so the check
        # also covers a real device prefix + edge suffix
        mid = cfg.n_layers // 2
        installed = cluster.engine.current_schedules().schedules
        cluster.engine.install_schedules(
            [dataclasses.replace(sc, split=np.full_like(sc.split, mid))
             for sc in installed])
        serve_and_check("interior cut", tokens)
    finally:
        split_runtime.device_forward = real_device
        split_runtime.edge_forward = real_edge
        cluster.stop(drain=False)


def phase_four_chips(spec, seed):
    """B cells sharded over a 4-chip ``cells`` mesh against the
    single-device reference backend."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core import ligd, network, profiles
    from repro.core.era import Weights
    from repro.distributed import solver_mesh

    # a fifth of the paper's U and M: the single-device reference backend
    # solves the cells one after another, ~10 s at this size on one v5e,
    # and a step's cost grows as U²·M — ~125x that at paper scale
    cfg = network.NetworkConfig(n_users=250, n_subchannels=50)
    prof = profiles.transformer_profile(get_config("gemma-2b"), seq=SEQ)
    scns = [network.make_scenario(jax_key(seed + i), cfg)
            for i in range(SHARDED_CELLS)]
    q = jnp.full((SHARDED_CELLS, cfg.n_users), 0.4)
    mesh = solver_mesh.cells_mesh(4)
    print(f"  B={SHARDED_CELLS} U={cfg.n_users} M={cfg.n_subchannels} "
          f"max_steps={spec.max_steps} mesh={dict(mesh.shape)}", flush=True)
    placed = []
    real_sweep = solver_mesh.sharded_sweep

    def sharded_sweep(*args, **kw):
        out = real_sweep(*args, **kw)
        placed.append([(s.device, s.data.shape[0])
                       for s in out.iters.addressable_shards])
        return out

    solver_mesh.sharded_sweep = sharded_sweep
    try:
        t0 = time.perf_counter()
        got = ligd.solve_batch(scns, prof, q, Weights(),
                               spec=spec.replace(backend="sharded",
                                                 mesh=mesh))
        t_sharded = time.perf_counter() - t0
    finally:
        solver_mesh.sharded_sweep = real_sweep
    t0 = time.perf_counter()
    want = ligd.solve_batch(scns, prof, q, Weights(),
                            spec=spec.replace(backend="reference"))
    t_ref = time.perf_counter() - t0
    shards = placed[-1]
    devices = {d for d, _ in shards}
    print(f"  lanes per device: {[(d.id, n) for d, n in shards]}", flush=True)
    check(len(devices) == 4
          and all(n == SHARDED_CELLS // 4 for _, n in shards),
          f"cells did not spread over 4 devices: {shards}")
    g_err = max(float(np.max(np.abs(a.gamma_by_layer - b.gamma_by_layer)
                             / np.abs(b.gamma_by_layer)))
                for a, b in zip(got, want))
    a_err = max(_scaled_err(getattr(a.alloc, k), getattr(b.alloc, k))
                for a, b in zip(got, want) for k in b.alloc._fields)
    same_s = all(np.array_equal(a.s, b.s) for a, b in zip(got, want))
    same_it = all(np.array_equal(a.iters_by_layer, b.iters_by_layer)
                  for a, b in zip(got, want))
    print(f"  sharded vs reference: same splits={same_s} same iterations="
          f"{same_it} Γ rel err={g_err!r} max scaled alloc err={a_err!r} "
          f"(first-call wall s: sharded={t_sharded!r} reference={t_ref!r})",
          flush=True)
    check(same_s and same_it, "sharded and reference solves differ")
    check(g_err <= SHARD_RTOL, f"Γ rel err {g_err}")
    check(a_err <= SHARD_ALLOC_ATOL, f"alloc err {a_err}")


# -------------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded solve on 4 chips against "
                         "the one-chip reference backend")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no program beside this script ({src}/repro)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch import platform
    platform.enable_compile_cache()
    Clock.install()
    from repro.core import ligd

    spec = ligd.SolverSpec(step_impl="fused", max_steps=MAX_STEPS)
    try:
        with Clock("a device"):
            device = phase_device(4 if args.four_chips else 1)
        if args.four_chips:
            with Clock("four-chips sharded-vs-reference"):
                phase_four_chips(spec, args.seed)
        else:
            with Clock("b solver"):
                phase_solver(spec, args.seed)
            with Clock("c serving"):
                phase_serving(spec, args.seed)
    except AssertionError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
