"""SpreadFGL's main path on a TPU, checked against the plain reference.

  python chip_smoke.py               # one chip: phases A and B
  python chip_smoke.py --four-chips  # four chips: the edge-mesh path only

Every trainer is set up by ``repro.launch.fgl_train.build``, the code the
``fgl_train`` CLI runs (synthetic Table-I graph -> client partition ->
config -> registered method), and trained with ``FGLTrainer.fit``.

- Phase A, paper shape: Cora stand-in at full size (2,708 nodes, 1,433
  features), SpreadFGL on a ring of N=3 servers, M=6 clients, hidden 64,
  ``kernel_impl="pallas"``, 3 rounds, imputing every round, at the CLI's
  default matmul precision. Compared with the same run at
  ``kernel_impl="reference"`` under ``jax.default_matmul_precision
  ("highest")``: the kernels compute in f32 whatever the context, and the
  reference must too. On identical inputs each kernel must meet f32
  rounding bounds (``sage_aggregate`` within ``sum_tolerance``, ``sim_topk``
  under ``ring_topk.topk_violations`` at ``dot_tolerance``). Between the two
  whole trainers the pallas side's XLA matmuls round their inputs to bf16,
  so round-0 embeddings must agree within ``EMB_TOL``, round-0 link
  proposals (``SpreadImputation.server_outputs``) meet the top-k contract
  at the tolerance those embeddings imply (``link_tolerance``), and the
  histories stay within ``LOSS_RTOL``/``ACC_TOL``.
- Phase B, widest Table-I graph: CoauthorCS at full size (18,333 nodes,
  6,805 features, 15 classes), same layout, pallas, 1 round, default
  precision.
- ``--four-chips``: ``spreadfgl_gossip`` at Cora full size, N=4, M=8,
  gossip every 4 rounds, with the edge mesh and the candidate ring on 4
  chips, against the same run without a mesh on one chip; plus the ring
  top-k against the single-device search on the same embeddings. Both sides
  run at the CLI's default precision.

Both phases check that the compiled local and impute programs hold a
``tpu_custom_call`` (the Pallas kernels, not an interpreted or reference
path). The script refuses to run unless JAX's backend is a TPU. A failed
check is reported and the run goes on, so one run shows every number; the
script then exits 1. The last line of standard output is a JSON object,
printed only when every check passed.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import pathlib
import sys
import time

import jax
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent

# Round-0 embeddings (softmax rows) of the pallas trainer at default
# precision against the reference trainer in f32: XLA rounds the dense
# layers' inputs to bf16 (unit roundoff 2^-9) under logits up to ~26. An
# f32 emulation of that rounding on the host moves entries by up to 0.028.
EMB_TOL = 0.06
# Histories after imputation rounds: bf16 training plus near-tie links
# resolved the other way rewire client graphs, so some test nodes flip. The
# bounds catch a broken path (accuracy falling towards chance), not rounding.
ACC_TOL = 0.05
LOSS_RTOL = 0.05

FAILED: list = []


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        log(f"chip_smoke: FAILED: {what}")
        FAILED.append(what)


def f32_exact():
    """The reference side's context: f32 matmuls, as the kernels compute."""
    return jax.default_matmul_precision("highest")


def build(*argv: str):
    """``fgl_train``'s own set-up: returns (trainer, batch)."""
    from repro.launch import fgl_train
    return fgl_train.build(fgl_train.parse_args(list(argv)))


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def check_kernels(tr, state, label: str) -> None:
    """The compiled local and impute programs must hold the Pallas kernels."""
    local = tr._local_fn.lower(state.params, state.opt_state,
                               state.batch).compile().as_text()
    impute = tr._impute_fn.lower(state).compile().as_text()
    for name, text in (("local", local), ("impute", impute)):
        found = "tpu_custom_call" in text
        log(f"[{label}] {name} program: tpu_custom_call "
            f"{'present' if found else 'MISSING'}")
        check(found, f"{label} {name} program has no Pallas kernel")


def check_history(hist, label: str) -> None:
    for k in ("loss", "acc", "f1"):
        check(all(math.isfinite(v) for v in hist[k]), f"{label} {k} not finite")
    check(all(0.0 <= a <= 1.0 for a in hist["acc"]), f"{label} acc out of range")


def compare_histories(got, ref, label: str) -> None:
    d_loss = max(abs(a - b) for a, b in zip(got["loss"], ref["loss"]))
    d_acc = max(abs(a - b) for a, b in zip(got["acc"], ref["acc"]))
    bound = LOSS_RTOL * max(abs(v) for v in ref["loss"]) + 1e-4
    log(f"[{label}] history max |d loss|={d_loss!r} (bound {bound!r}), "
        f"max |d acc|={d_acc!r} (bound {ACC_TOL})")
    check(d_loss <= bound, f"{label} loss history diverged")
    check(d_acc <= ACC_TOL, f"{label} accuracy history diverged")


def search_inputs(tr, state):
    """The round's own [N]-stacked search inputs, on the host."""
    fn = jax.jit(lambda st: tr.imputation.search_inputs(tr, st))
    return tuple(np.asarray(v) for v in fn(state))


def sum_tolerance(n: int, scale: float) -> float:
    """Two f32 evaluations of a weighted mean of n terms of size <= scale
    (weights summing to at most 1) differ by at most 2·n·u·scale = n·ε·scale;
    four times that, as in ``ring_topk.dot_tolerance``."""
    return float(4 * n * np.finfo(np.float32).eps * scale)


def link_tolerance(h, h_ref, rows) -> float:
    """Top-k contract tolerance between searches over two embeddings.

    For u, v with row errors e = |h - h_ref| <= E and norms <= P (h), R
    (h_ref), |h_u·h_v - ref_u·ref_v| <= E·(P + R) =: T1, plus f32 rounding
    (``dot_tolerance``). Rank-r scores then differ by <= T1, and a chosen
    candidate's exact reference similarity is within 2·T1 of the
    reference's rank-r score: ``topk_violations`` gets 2·T1.
    """
    from repro.core.ring_topk import dot_tolerance
    h, h_ref = h[rows], h_ref[rows]
    e = np.max(np.linalg.norm(h.astype(np.float64) - h_ref, axis=-1))
    p = np.max(np.linalg.norm(h.astype(np.float64), axis=-1))
    r = np.max(np.linalg.norm(h_ref.astype(np.float64), axis=-1))
    return float(2 * (e * (p + r) + max(dot_tolerance(h), dot_tolerance(h_ref))))


def link_report(views, got, ref, tol, label: str) -> None:
    from repro.core.ring_topk import topk_violations
    h, _, cid, tmask = views
    rep = topk_violations(h, cid, tmask, got[0], got[1], ref[0], ref[1],
                          tol=tol)
    log(f"[{label}] max |d score|={rep['max_score_diff']!r} "
        f"index diffs={rep['index_diffs']} violations={rep['violations']} "
        f"(tol {tol!r})")
    check(rep["violations"] == 0, f"{label}: top-k contract violated")


def search_fn(tr, impl: str, mesh=None):
    """The similarity search of the imputation round, batched over servers."""
    from repro.core import imputation
    k = tr.cfg.top_k_links

    def one(h, fmask, cid, tmask):
        return imputation.similarity_topk(h, fmask, cid, k, kernel_impl=impl,
                                          target_mask=tmask)
    if mesh is None:
        return jax.jit(jax.vmap(one))
    return jax.jit(lambda h, fmask, cid, tmask: imputation.similarity_topk(
        h, fmask, cid, k, target_mask=tmask, mesh=mesh))


def aggregate_report(batch) -> None:
    """``sage_aggregate`` on the real client graphs, kernel vs f32 reference."""
    from repro.core import gnn

    def agg(impl):
        return jax.jit(jax.vmap(lambda x, adj, m: gnn.aggregate(
            gnn.normalize_adjacency(adj, m), x * m[:, None], impl)))
    got = np.asarray(agg("pallas")(batch.x, batch.adj, batch.node_mask))
    with f32_exact():
        ref = np.asarray(agg("reference")(batch.x, batch.adj,
                                          batch.node_mask))
    tol = sum_tolerance(batch.n_pad, float(np.max(np.abs(batch.x))))
    d = float(np.max(np.abs(got - ref)))
    log(f"[A sage_aggregate on client graphs, kernel vs reference] "
        f"max |d|={d!r} (tol {tol!r})")
    check(d <= tol, "A sage_aggregate kernel disagrees with the reference")


def phase_a() -> None:
    from repro.core.ring_topk import dot_tolerance
    argv = ("--dataset", "cora", "--scale", "1.0", "--servers", "3",
            "--clients", "6", "-K", "1")
    key = jax.random.key(0)
    t0 = time.perf_counter()
    tr_p, batch = build(*argv, "--impl", "pallas")
    tr_r, _ = build(*argv, "--impl", "reference")
    log(f"[A] set-up: {time.perf_counter() - t0:.3f} s, ClientBatch x "
        f"{tuple(batch.x.shape)} adj {tuple(batch.adj.shape)}, hidden "
        f"{tr_p.cfg.hidden_dim}")
    state0 = tr_p.init(key, batch)

    # Each kernel alone, on identical inputs.
    aggregate_report(batch)
    with f32_exact():
        views = search_inputs(tr_r, state0)
        ref = search_fn(tr_r, "reference")(*views)
    got = search_fn(tr_p, "pallas")(*views)
    link_report(views, got, ref, dot_tolerance(views[0]),
                "A search on identical embeddings, sim_topk vs reference")

    # The two trainers' own round 0.
    views_p = search_inputs(tr_p, state0)
    rows = views[1] > 0
    d_emb = float(np.max(np.abs(views_p[0][rows] - views[0][rows])))
    log(f"[A] round-0 embeddings max |d|={d_emb!r} (bound {EMB_TOL})")
    check(d_emb <= EMB_TOL, "A round-0 embeddings diverged")
    outs = {}
    for name, tr, ctx in (("pallas", tr_p, contextlib.nullcontext),
                          ("reference", tr_r, f32_exact)):
        with ctx():
            fn = jax.jit(lambda st, tr=tr: tr.imputation.server_outputs(tr, st))
            (_, _, _, _, s, i, xb), _ = fn(state0)
            outs[name] = (np.asarray(s), np.asarray(i))
        check(bool(np.all(np.isfinite(np.asarray(xb)))),
              f"A {name} imputed features not finite")
    link_report(views, outs["pallas"], outs["reference"],
                link_tolerance(views_p[0], views[0], rows),
                "A round-0 links, pallas vs reference")

    hists = {}
    for name, tr, ctx in (("pallas", tr_p, contextlib.nullcontext),
                          ("reference", tr_r, f32_exact)):
        t0 = time.perf_counter()
        with ctx():
            state, hists[name] = tr.fit(key, batch, rounds=3)
        log(f"[A] {name} fit, 3 rounds incl. compile: "
            f"{time.perf_counter() - t0:.3f} s; loss {hists[name]['loss']} "
            f"acc {hists[name]['acc']}")
        check_history(hists[name], f"A {name}")
        if name == "pallas":
            check_kernels(tr, state, "A")
    compare_histories(hists["pallas"], hists["reference"], "A")
    log(f"[A] done; peak_bytes_in_use={peak_bytes()}")


def phase_b() -> None:
    t0 = time.perf_counter()
    tr, batch = build("--dataset", "coauthor_cs", "--scale", "1.0",
                      "--servers", "3", "--clients", "6", "-K", "1",
                      "--impl", "pallas")
    log(f"[B] set-up: {time.perf_counter() - t0:.3f} s, ClientBatch x "
        f"{tuple(batch.x.shape)} adj {tuple(batch.adj.shape)}, hidden "
        f"{tr.cfg.hidden_dim}")
    t0 = time.perf_counter()
    state, hist = tr.fit(jax.random.key(0), batch, rounds=1)
    log(f"[B] pallas fit, 1 round incl. compile: "
        f"{time.perf_counter() - t0:.3f} s; loss {hist['loss']} "
        f"acc {hist['acc']}")
    check_history(hist, "B")
    check_kernels(tr, state, "B")
    log(f"[B] done; peak_bytes_in_use={peak_bytes()}")


def four_chips() -> None:
    from repro.core.ring_topk import dot_tolerance
    if len(jax.devices()) != 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 devices, found "
                         f"{len(jax.devices())}")
    argv = ("--dataset", "cora", "--scale", "1.0", "--servers", "4",
            "--clients", "8", "-K", "1", "--impl", "pallas",
            "--gossip-every", "4")
    key = jax.random.key(0)
    t0 = time.perf_counter()
    tr_m, batch = build(*argv, "--edge-mesh", "--sim-shard")
    tr_1, _ = build(*argv)
    log(f"[4] set-up: {time.perf_counter() - t0:.3f} s")
    state = tr_m.init(key, batch)
    spans = sorted({d.id for leaf in jax.tree.leaves(state.ae_params)
                    for d in leaf.devices()})
    log(f"[4] stacked [N] generator state spans device(s) {spans}")
    check(len(spans) == 4, "stacked state does not span 4 devices")

    views = search_inputs(tr_1, state)
    ring = search_fn(tr_m, "pallas", mesh=tr_m.imputation.sim_mesh)
    single = search_fn(tr_1, "pallas")
    link_report(views, ring(*views), single(*views), dot_tolerance(views[0]),
                "4 ring top-k (4 chips) vs single-device sim_topk")

    hists = {}
    for name, tr in (("mesh", tr_m), ("one-chip", tr_1)):
        t0 = time.perf_counter()
        _, hists[name] = tr.fit(key, batch, rounds=4)
        log(f"[4] {name} fit, 4 rounds incl. compile: "
            f"{time.perf_counter() - t0:.3f} s; loss {hists[name]['loss']} "
            f"acc {hists[name]['acc']}")
        check_history(hists[name], f"4 {name}")
    compare_histories(hists["mesh"], hists["one-chip"], "4")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip edge-mesh path and its "
                         "one-chip comparison")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT} is not a checkout of this repository "
              f"(no src/repro)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); this check runs only on the chip",
              file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    dev = jax.devices()[0]
    log(f"[chip] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {compile_cache.enable()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips()
    else:
        for phase in (phase_a, phase_b):
            t = time.perf_counter()
            phase()
            log(f"[chip] {phase.__name__}: {time.perf_counter() - t:.3f} s")
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}",
              file=sys.stderr)
        return 1
    log(f"[chip] all checks passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
