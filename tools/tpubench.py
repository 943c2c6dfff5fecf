"""Piecewise device microbenchmark for the search-kernel ops.

Times one full kernel level at several frontier widths, then each
pipeline stage in isolation (expand, hash, the dedup sort in both
variadic and packed forms, gathers in row-major and transposed layouts,
stream compaction), emitting one JSON line per measurement.  The point:
locate WHERE per-level cost explodes with width on a given backend — on
TPU the jump from F=1024 to F=8192 was measured at ~1600x (0.02 ->
32 ms/level) while CPU scales linearly, so some op hits a cliff that
linear reasoning cannot find.  Run this on the device, read the table,
then optimize the guilty op.

Usage:
    python tools/tpubench.py [--widths 1024,8192,65536] [--repeat 5]
    JAX_PLATFORMS=cpu python tools/tpubench.py   # CPU comparison
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402


def bench_one(name: str, fn, *args, repeat: int = 5) -> dict:
    f = jax.jit(fn)
    t0 = time.perf_counter()
    out = f(*args)
    jax.block_until_ready(out)
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = f(*args)
    jax.block_until_ready(out)
    ms = (time.perf_counter() - t0) / repeat * 1000
    row = {"op": name, "ms": round(ms, 4),
           "compile_s": round(t_compile, 2)}
    print(json.dumps(row), flush=True)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--widths", default="1024,8192,65536")
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--levels", type=int, default=64)
    args = ap.parse_args()
    widths = [int(w) for w in args.widths.split(",")]
    rep = args.repeat

    print(json.dumps({"backend": jax.default_backend(),
                      "device": str(jax.devices()[0])}), flush=True)

    import bench as hbench
    from jepsen_tpu.checker import linearizable as lin

    seq, model = hbench.make_seq("10k")
    es = lin.encode_search(seq)

    for F in widths:
        dims = lin.choose_dims(es, model, frontier=F)
        esp = lin.pad_search(es, dims.n_det_pad, dims.n_crash_pad)
        K, WORDS = dims.k, dims.words
        S = 4 * F
        rng = np.random.default_rng(0)

        # --- full kernel level, BOTH dominance prunes ------------------
        # the all-pairs prune exists to beat the sort pipeline's per-op
        # overhead floor at narrow widths; these paired rows are the
        # decisive on-chip measurement (skip all-pairs where its [M,M]
        # intermediates get silly — auto never picks it there either)
        kargs = lin.search_args(esp, es)
        lvls = jnp.int32(args.levels)
        modes = ["sort"] + (["allpairs"] if S <= lin._ALLPAIRS_MAX
                            else [])
        mode0 = lin._DOMINANCE_MODE
        for mode in modes:
            lin._DOMINANCE_MODE = mode
            try:
                # what the selector ACTUALLY chooses per site under
                # this mode (a forced "allpairs" can still fall back to
                # sort past the element budget — the row must say so)
                ap_cl = lin._use_allpairs(2 * F)
                ap_det = lin._use_allpairs(4 * F)
                fn = lin.get_kernel(model, dims)
                carry = tuple(jnp.asarray(c)
                              for c in lin._init_carry(dims, model))

                n_args = len(kargs)

                def level_fn(*a):
                    return fn(*a[:n_args], jnp.int32(10**9), lvls,
                              jnp.bool_(False), *a[n_args:])

                t0 = time.perf_counter()
                out = level_fn(*kargs, *carry)
                jax.block_until_ready(out)
                t_compile = time.perf_counter() - t0
                # repeat like every other row: a single-shot reading
                # straight after a compile is an artifact, not physics
                dts = []
                for _ in range(rep):
                    t0 = time.perf_counter()
                    out = level_fn(*kargs, *carry)
                    jax.block_until_ready(out)
                    dts.append(time.perf_counter() - t0)
            finally:
                lin._DOMINANCE_MODE = mode0
            _fr, count, status, configs, max_depth, ovf = out
            # levels actually executed (each level linearizes one det
            # op); the while_loop exits early on frontier death /
            # verdict.  max_depth snapshots the ENTRY frontier of the
            # last body iteration (depth starts at 0), so L executed
            # levels report max_depth = L-1
            lvls_run = int(max_depth) + 1
            print(json.dumps({
                "op": f"kernel-{args.levels}-levels", "F": F, "K": K,
                "WORDS": WORDS, "dominance": mode,
                "allpairs_closure": ap_cl, "allpairs_det": ap_det,
                "ms_per_level": round(min(dts) / lvls_run * 1000, 4),
                "ms_per_level_mean": round(sum(dts) / len(dts)
                                           / lvls_run * 1000, 4),
                "levels_run": lvls_run,
                "carry": {"count": int(count), "status": int(status),
                          "configs": int(configs), "ovf": bool(ovf)},
                "compile_s": round(t_compile, 2)}), flush=True)

        # --- isolated pieces at the same shapes ------------------------
        keys32 = jnp.asarray(
            rng.integers(0, 2**31, S).astype(np.uint32))
        cfgs = jnp.asarray(
            rng.integers(0, 1000, (S, WORDS)).astype(np.int32))
        cfgsT = jnp.asarray(np.asarray(cfgs).T.copy())
        idx = jnp.asarray(rng.integers(0, S, S).astype(np.int32))
        mask = jnp.asarray(rng.random(S) < 0.2)
        frontier = jnp.asarray(
            rng.integers(0, 1000, (F, WORDS)).astype(np.int32))
        alive = jnp.ones(F, bool)

        pieces = lin._make_kernel_pieces(model, dims)

        def mask_fn(fr, al):
            base, sargs = lin._slice_tables(kargs, fr, al,
                                            w2p=pieces["w2p"])
            v, c, ns, g = pieces["expand_mask"](fr, al, base, *sargs)
            return v.sum(), c.sum(), ns.sum(), g.sum()

        bench_one(f"expand_mask F={F}", mask_fn, frontier, alive,
                  repeat=rep)

        def succ_fn(fr, al):
            v, c, ns, g = lin._level_mask(pieces, kargs, fr, al)
            cc, cv, n = lin._succ_block(pieces, fr,
                                        v.reshape(F * K), c, ns, S, K)
            return cc.sum(), cv.sum()

        bench_one(f"expand+succ(S) F={F}", succ_fn, frontier,
                  alive, repeat=rep)
        bench_one(f"hash S={S}",
                  lambda c: lin._hash_words(c.astype(jnp.uint32),
                                            0x9E3779B1).sum(),
                  cfgs, repeat=rep)
        # the production dominance sort is 3-operand / 2-key
        # (_sort_dominance); these two isolate the raw lax.sort cost at
        # the same row count for single- vs multi-operand forms
        bench_one(
            f"sort-variadic S={S}",
            lambda k: lax.sort((k, jnp.arange(S, dtype=jnp.int32)),
                               num_keys=1),
            keys32, repeat=rep)
        bench_one(f"sort-packed32 S={S}", lambda k: lax.sort(k),
                  keys32, repeat=rep)
        bench_one(f"gather-rows [S,{WORDS}] S={S}",
                  lambda c, i: jnp.take(c, i, axis=0).sum(), cfgs, idx,
                  repeat=rep)
        bench_one(f"gather-cols [{WORDS},S] S={S}",
                  lambda c, i: jnp.take(c, i, axis=1).sum(), cfgsT, idx,
                  repeat=rep)
        bench_one(f"compact_indices S={S}",
                  lambda m: lin._compact_indices(m, S // 4), mask,
                  repeat=rep)

        def dom_fn(c, m):
            pwh, popc = lin._pw_parts(c, dims)
            kept, sc, perm = lin._sort_dominance(pwh, popc, m, c, S,
                                                 dims)
            return kept.sum(), sc.sum()

        bench_one(f"sort_dominance S={S}", dom_fn, cfgs, mask,
                  repeat=rep)

        # 64 chained prunes in ONE dispatch: the standalone rows above
        # are floored by the per-dispatch host cost; these isolate
        # the true in-kernel per-application cost of each prune form
        # (the chain is data-dependent, so nothing hoists)
        def loop64(prune_fn):
            def run(c, m):
                def body(_i, carry):
                    cc, mm = carry
                    kept, sc = prune_fn(cc, mm)
                    # the output must differ from the input or XLA
                    # recognizes the loop body as identity and deletes
                    # the chain (observed: a 0.0005 ms "prune")
                    return sc + kept[:, None].astype(jnp.int32), mm
                return lax.fori_loop(0, 64, body, (c, m))[0].sum()
            return run

        def sort_prune(c, m):
            pwh, popc = lin._pw_parts(c, dims)
            kept, sc, _ = lin._sort_dominance(pwh, popc, m, c, S, dims)
            return kept, sc

        bench_one(f"sort_dominance-loop64 S={S}", loop64(sort_prune),
                  cfgs, mask, repeat=rep)
        if S <= lin._ALLPAIRS_MAX:
            def ap_prune(c, m):
                kept = lin._allpairs_dominance(c, m, dims)
                return kept, c

            bench_one(f"allpairs_dominance-loop64 S={S}",
                      loop64(ap_prune), cfgs, mask, repeat=rep)
        bench_one(f"neighbor-dedup S={S}",
                  lambda c: (jnp.all(c[1:] == c[:-1], axis=1)).sum(),
                  cfgs, repeat=rep)

    # --- engine-paired rows: pallas level-loop vs XLA step -----------
    # The pallas kernel (checker/pallas_level.py) fuses the whole level
    # loop into one device op to beat the ~1.3 ms/level op-count floor
    # (docs/perf-notes.md r4).  mutex2k is the eligibility-friendly
    # history (window 32); these rows are the decisive on-chip A/B.
    # A Mosaic lowering failure must emit a diagnostic row, not kill
    # the sweep — it would be the first hardware contact for the path.
    from jepsen_tpu.checker import pallas_level as plev

    seqm, modelm = hbench.make_seq("mutex2k")
    esm = lin.encode_search(seqm)
    for F in (16, 64):
        dimsm = lin.choose_dims(esm, modelm, frontier=F)
        if not plev.eligible(modelm, dimsm):
            print(json.dumps({"op": "engine-pair", "F": F,
                              "skipped": "ineligible dims",
                              "dims": str(dimsm)}), flush=True)
            continue
        espm = lin.pad_search(esm, dimsm.n_det_pad, dimsm.n_crash_pad)
        kargsm = lin.search_args(espm, esm)
        mode0 = lin._DOMINANCE_MODE
        for engine in ("xla", "pallas"):
            try:
                lin._DOMINANCE_MODE = "allpairs"
                if engine == "pallas":
                    step = jax.jit(plev.build_pallas_step_fn(
                        modelm, dimsm,
                        interpret=jax.default_backend() != "tpu"))
                else:
                    step = jax.jit(lin.build_search_step_fn(modelm,
                                                            dimsm))
                carry = tuple(jnp.asarray(c)
                              for c in lin._init_carry(dimsm, modelm))
                t0 = time.perf_counter()
                out = step(*kargsm, jnp.int32(10**9),
                           jnp.int32(args.levels), jnp.bool_(False),
                           *carry)
                jax.block_until_ready(out)
                t_compile = time.perf_counter() - t0
                dts = []
                for _ in range(rep):
                    t0 = time.perf_counter()
                    out = step(*kargsm, jnp.int32(10**9),
                               jnp.int32(args.levels), jnp.bool_(False),
                               *carry)
                    jax.block_until_ready(out)
                    dts.append(time.perf_counter() - t0)
                lvls_run = int(out[4]) + 1
                print(json.dumps({
                    "op": f"engine-{args.levels}-levels", "F": F,
                    "engine": engine, "history": "mutex2k",
                    "ms_per_level": round(min(dts) / lvls_run * 1000,
                                          4),
                    "levels_run": lvls_run,
                    "carry": {"count": int(out[1]),
                              "status": int(out[2]),
                              "configs": int(out[3]),
                              "ovf": bool(out[5])},
                    "compile_s": round(t_compile, 2)}), flush=True)
            except Exception as e:  # noqa: BLE001 — diagnostic row
                print(json.dumps({"op": f"engine-{args.levels}-levels",
                                  "F": F, "engine": engine,
                                  "error": repr(e)[:500]}), flush=True)
            finally:
                lin._DOMINANCE_MODE = mode0


if __name__ == "__main__":
    main()
