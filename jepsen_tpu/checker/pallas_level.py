"""Pallas TPU level-loop kernel — the whole BFS slice as ONE device op.

Why this exists: the XLA step kernel's level body compiles to ~70-140
fused computations, and on a TPU each one pays a fixed launch
overhead, so the per-level cost has a floor no matter how narrow the
live frontier is.  Depth-bound searches (mutex2k: 1,971 sequential
levels; 10k: ~9.8k) are therefore op-COUNT-bound, not compute-bound.  This module re-expresses the entire slice loop —
``lvl_cap`` levels of mask/closure/expand/prune/compact — as a single
``pl.pallas_call`` whose interior is ~dozens of large VPU/MXU
operations per level with no per-op dispatch overhead.

Design notes (the reference's analog of this engine is knossos's JVM
search loop, jepsen/src/jepsen/checker.clj:114-139 — redesigned here
for the TPU's compute model rather than translated):

* The frontier lives UNPACKED inside the kernel: window/crash masks as
  [F, W]/[F, NC] 0/1 planes instead of packed u32 words.  Packing
  exists for host/HBM compactness; in VMEM the unpacked planes turn
  every bit-twiddle (funnel shifts, trailing-ones, kth-set-bit) into
  plain elementwise/matmul algebra the VPU/MXU like.  Pack/unpack
  happens once per SLICE at the XLA boundary, amortized over
  ``lvl_cap`` levels.
* Every gather is an exact f32 MATMUL (MXU, HIGHEST precision), never
  a dynamic gather: table windows are read with one 128-lane-aligned
  dynamic slice per level and shifted per frontier row by a barrel
  shifter (`_shift_rows`: one 0/1 shift-matrix matmul per bit of the
  offset); row gathers are one-hot contractions.  Values that can
  exceed f32's 2^24 integer-exact range (model-state words, op v1/v2)
  go through a 12-bit limb split — two exact f32 matmuls, recombined
  in int32.  Comparison tables (inv/ret/suffix-min) are clamped to
  CLAMP_INF < 2^24 at the boundary (all real positions are < 2^17, so
  every comparison is preserved).
* Mosaic-shaped throughout (tests/test_chip_compile.py compiles it for
  a described v5e): no selects or concatenations of booleans (boolean
  algebra, int32 joins), no [M, 1] -> [1, M] reshapes (one transpose
  of a stacked block instead), no lane slice at an unaligned offset,
  and a scoped-VMEM limit raised to VMEM_LIMIT_BYTES.
* Stream compaction is hierarchical: per-row counts -> triangular-
  matmul cumsum -> `[cap, F]` row one-hot -> `[cap, L]` lane one-hot
  (two small matmuls + compares).  No sorts anywhere.
* Dominance pruning is the exact all-pairs rule (mirrors
  `_allpairs_dominance` in linearizable.py): equality via popcount
  matmul identities, crash-subset via |cr_j| - |cr_i ∩ cr_j| == 0.
* Control flow is `fori_loop` + `@pl.when` predication only (Mosaic-
  safe): the level loop runs ``lvl_cap`` rounds gated on a `running`
  scalar, the crash closure runs ``n_crash+1`` rounds gated on a
  `progress` scalar — predicated-off rounds skip at runtime.

Semantics contract: bit-for-bit the SAME search as
`build_search_step_fn` with the all-pairs prune — identical survivor
order (f-major, lane-ascending), identical configs counts, identical
overflow/bail/revert behavior — so the slice driver, checkpoints, and
escalation ladder work unchanged.  Differential tests enforce this
(tests/test_pallas_level.py).

Eligibility: F <= 64, W <= 64, NC <= 64, state_width <= 4, and a model
whose ``jstep`` is elementwise (register / cas-register / mutex /
noop).  Wider rungs fall back to the XLA kernel — the pallas engine
exists for the narrow, depth-dominated regime that floors on op count.

Phase-2 reductions (the device must-order mask and the dead-value
dedup rewrite) also route to the XLA kernel: the mask's per-lane
linearized-predecessor test costs ~W predicated plane ops per
predecessor slot on unpacked planes (there is no cheap batched
win[q - p] gather without a 3-D reduce Mosaic dislikes), which would
triple exactly the op count this kernel exists to eliminate — while on
the XLA kernel the same test is a handful of fused gathers.  So
``eligible`` declines ``masked``/``dedup`` searches and `get_kernel`
builds the XLA step for them; the step signature still carries the
reduction planes (ignored) so every driver stays signature-uniform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

#: internal "infinity" for clamped comparison tables — above every real
#: position (< 2^17) and exactly representable in f32
CLAMP_INF = np.int32(1 << 23)

#: scoped-VMEM ceiling handed to Mosaic.  The default scoped limit
#: (16 MiB on v5e) is below what the kernel's one-hot planes take at
#: the eligible() limits; v5e has 128 MiB of VMEM per core
VMEM_LIMIT_BYTES = 96 << 20

#: models whose jstep is elementwise (vmaps to Mosaic-friendly ops)
SAFE_MODELS = frozenset({"register", "cas-register", "mutex", "noop"})

#: scalar-scratch slots (12-14 are telemetry-only: level cursor,
#: per-level crash-closure round count, post-closure occupancy)
(_CNT, _STA, _CFG, _MD, _OVF, _RUN, _FOUND, _CLGO,
 _CNT0, _CFG0, _MD0, _OVF0, _TLVL, _TROUNDS, _TOCC) = range(15)


def eligible(model, dims, *, masked: bool = False,
             dedup: bool = False) -> bool:
    # masked/dedup searches run the XLA kernel (see module doc): the
    # reduction checks are matmul-hostile on unpacked planes and would
    # triple the per-level op count this kernel exists to eliminate
    return (not masked and not dedup
            and model.name in SAFE_MODELS
            and dims.frontier <= 64
            and dims.window <= 64
            and dims.n_crash_pad <= 64
            and dims.state_width <= 4)


def _f32(x):
    return x.astype(jnp.float32)


def _mm(a, b):
    """f32 matmul, always through the MXU contraction path."""
    return lax.dot_general(_f32(a), _f32(b), (((1,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _gather_i32(oh, plane):
    """Exact int32 gather `oh @ plane` for arbitrary int32 values via a
    12-bit limb split (each oh row has at most one nonzero)."""
    lo = _f32(jnp.bitwise_and(plane, 0xFFF))
    hi = _f32(jnp.right_shift(plane, 12))
    return (_mm(oh, hi).astype(jnp.int32) * 4096
            + _mm(oh, lo).astype(jnp.int32))


def _iota(n, axis, shape):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _transpose_cols(cols):
    """[M, 1] int32 columns -> the matching [1, M] rows, through ONE
    transpose of the block they stack into (padded to 8 columns)."""
    m = cols[0].shape[0]
    k = len(cols)
    pad = -(-k // 8) * 8 - k
    blk = jnp.concatenate(
        [c.astype(jnp.int32) for c in cols]
        + ([jnp.zeros((m, pad), jnp.int32)] if pad else []), axis=1)
    t = blk.T
    return [t[i:i + 1, :] for i in range(k)]


def _shift_rows(x, off, w):
    """``out[r, l] = x[r, l + off[r]]`` for ``l < w``: a per-row left
    shift of an f32 plane ``x`` [R, N] by ``off`` [R, 1] (each in
    [0, N - w]), as a barrel shifter — per bit of the shift, highest
    first, one exact matmul by a constant 0/1 shift matrix, selected
    per row.  After the bits >= k are applied only the first
    ``w + k - 1`` lanes can still be read, so each stage narrows to
    that (rounded up to a 128-lane tile).  Mosaic neither slices lanes
    at an unaligned offset nor reshapes [R*w, 1] -> [R, w] cheaply."""
    n = x.shape[1]
    k = 1
    while 2 * k <= n - w:
        k *= 2
    while k >= 1:
        out_w = min(x.shape[1], -(-(w + k - 1) // 128) * 128)
        shk = _f32(_iota(0, 0, (x.shape[1], out_w))
                   == _iota(0, 1, (x.shape[1], out_w)) + k)
        x = jnp.where(jnp.bitwise_and(off, k) != 0, _mm(x, shk),
                      x[:, :out_w])
        k //= 2
    return x[:, :w]


def build_pallas_step_fn(model, dims, *, interpret: bool = False,
                         masked: bool = False,
                         telemetry: bool = False):
    """Build a slice-step function with `build_search_step_fn`'s exact
    signature, backed by one pallas_call running the whole level loop.

    ``masked`` is accepted for get_kernel symmetry but must be False —
    masked searches are not pallas-eligible (module doc); the step
    still ACCEPTS the reduction-plane arguments and ignores them, so
    drivers and differential tests stay signature-uniform.

    ``telemetry`` emits the per-level aux counter block (obs/
    telemetry.py schema) as an extra output, matching the XLA kernel's
    telemetry contract.  The block is built from pure elementwise
    one-hot adds on a tiny [TELE_ROWS, TELE_COLS] plane (no dynamic
    stores — Mosaic-safe), is write-only, and never feeds back into
    the search.  mask_killed / dedup_folds are structurally zero here:
    pallas-eligible searches carry no reductions by design."""
    if masked:
        raise ValueError("masked searches are not pallas-eligible; "
                         "build the XLA kernel instead (see "
                         "pallas_level.eligible)")
    from ..obs.telemetry import (C_EXP, C_GOAL, C_NEXT, C_OCC, C_OVF,
                                 C_ROUNDS, TELE_COLS, TELE_ROWS)
    F = dims.frontier
    W = dims.window
    NC = dims.n_crash_pad
    SW = dims.state_width
    WW = dims.win_words
    CW = dims.crash_words
    ND = dims.n_det_pad
    L = W + NC
    SCAP = 4 * F
    # Mosaic loads a dynamic lane window only at a 128-aligned offset,
    # so the table-window base is rounded DOWN to a 128-multiple and
    # the window grows by one lane tile to keep covering
    # [min_p, min_p + 2W + NC]; the tables are edge-padded at the XLA
    # boundary to ND2 lanes so a window never runs off their end
    W2P = -(-(2 * W + NC + 128) // 128) * 128
    ND2 = max(-(-(ND + 1) // 128) * 128, W2P)

    # constant unpack/pack index tables (host-side numpy)
    w_word = np.arange(W) // 32
    w_bit = np.arange(W) % 32
    c_word = np.arange(NC) // 32
    c_bit = np.arange(NC) % 32

    def kernel(scal, tf, tv1, tv2, tinv, tret, sfx, crf, crv1, crv2,
               crinv, p_in, win_in, crash_in, state_in,
               p_out, win_out, crash_out, state_out, scal_out,
               *rest):
        if telemetry:
            tele_out = rest[0]
            rest = rest[1:]
        (pc, wc, cc, stc, ps, ws, cs, sts, v2r, g2r, nsr, st) = rest
        n_det = scal[5, 0]
        n_crash = scal[6, 0]
        budget = scal[7, 0]
        lvl_cap = scal[8, 0]
        bail = scal[9, 0]

        pc[:] = p_in[:]
        wc[:] = win_in[:]
        cc[:] = crash_in[:]
        stc[:] = state_in[:]
        for i, slot in ((0, _CNT), (1, _STA), (2, _CFG), (3, _MD),
                        (4, _OVF)):
            st[slot, 0] = scal[i, 0]
        st[_RUN, 0] = jnp.where(
            (scal[1, 0] == -1) & (scal[0, 0] > 0)
            & (scal[2, 0] < budget)
            & ~((bail == 1) & (scal[4, 0] == 1)), 1, 0)
        if telemetry:
            tele_out[:] = jnp.zeros((TELE_ROWS, TELE_COLS), jnp.int32)
            st[_TLVL, 0] = 0

        lane_i = _iota(L, 1, (1, L))          # [1, L] candidate lane ids
        is_det_lane = lane_i < W

        def mask_phase():
            """Expand the CURRENT planes: valid/goal per candidate lane
            + successor model states.  Mirrors expand_mask_one
            (linearizable.py:1054) on unpacked planes, all lanes (no
            K-cap: the cap was a no-loss bound; S-cap still applies at
            compaction)."""
            count = st[_CNT, 0]
            p = pc[:]                          # [F, 1]
            win = wc[:]                        # [F, W]
            crash = cc[:]                      # [F, NC]
            state = stc[:]                     # [F, SW]
            aliv = _iota(F, 0, (F, 1)) < count
            base = jnp.min(jnp.where(aliv, p, CLAMP_INF))
            # 128-aligned so the pl.ds lane offsets lower (see W2P)
            base = (jnp.clip(base, 0, ND2 - W2P) // 128) * 128
            base = pl.multiple_of(base, 128)

            # max index read = (min_p - base) + 2W + NC <= 127 + 2W + NC
            # <= W2P - 1, suffix index included (it is <= n_det <= ND
            # < ND2).  Do NOT drop the +128 from W2P without removing
            # the base down-rounding.
            def win_row(tab):
                return tab[:, pl.ds(base, W2P)]          # [1, W2P]

            off = p - base                     # [F, 1]
            lw = _iota(W, 1, (1, W))
            # the det-lane tables t[p + l], l < W, as [F, W] planes:
            # every table's window row, stacked ([7F, W2P]), then each
            # row shifted left by its off.  Comparison tables are
            # clamped under 2^24, so they ride the f32 shifter whole;
            # op codes/values go as 12-bit limbs (see _gather_i32)
            rows = [win_row(tret), win_row(tinv), win_row(tf)]
            for t in (win_row(tv1), win_row(tv2)):
                rows += [jnp.bitwise_and(t, 0xFFF),
                         jnp.right_shift(t, 12)]
            planes = _shift_rows(
                jnp.concatenate([jnp.broadcast_to(_f32(r), (F, W2P))
                                 for r in rows], axis=0),
                jnp.concatenate([off] * len(rows), axis=0), W)
            blk = [planes[i * F:(i + 1) * F] for i in range(len(rows))]
            wret, winv = blk[0], blk[1]
            d_f = blk[2].astype(jnp.int32)
            d_v1 = (blk[4].astype(jnp.int32) * 4096
                    + blk[3].astype(jnp.int32))
            d_v2 = (blk[6].astype(jnp.int32) * 4096
                    + blk[5].astype(jnp.int32))
            pos_in = (p + lw) < n_det          # [F, W]
            no_win = win == 0
            INF = jnp.float32(CLAMP_INF)
            wret_eff = jnp.where(pos_in & no_win, wret, INF)
            m1 = jnp.min(wret_eff, axis=1, keepdims=True)
            am = jnp.min(jnp.where(wret_eff == m1, lw, W), axis=1,
                         keepdims=True)
            m2 = jnp.min(jnp.where(lw == am, INF, wret_eff), axis=1,
                         keepdims=True)
            # suffix-min beyond the window
            sidx = jnp.minimum(p + W, n_det) - base        # [F, 1]
            sfxv = _f32(jnp.sum(
                jnp.where(sidx == _iota(W2P, 1, (1, W2P)),
                          win_row(sfx), 0),
                axis=1, keepdims=True))                    # [F, 1]
            m1_tot = jnp.minimum(m1, sfxv)
            excl_w = jnp.where(lw == am, m2, m1)
            excl_tot = jnp.minimum(excl_w, sfxv)
            det_en = pos_in & no_win & (winv < excl_tot)

            cl = _iota(NC, 1, (1, NC))
            crinv_f = _f32(crinv[:])                     # [1, NC]
            crash_en = ((cl < n_crash) & (crash == 0)
                        & (crinv_f < m1_tot))

            # candidate op tables on all L lanes
            c_f = jnp.broadcast_to(crf[:], (F, NC))
            c_v1 = jnp.broadcast_to(crv1[:], (F, NC))
            c_v2 = jnp.broadcast_to(crv2[:], (F, NC))
            aF = jnp.concatenate([d_f, c_f], axis=1)
            aV1 = jnp.concatenate([d_v1, c_v1], axis=1)
            aV2 = jnp.concatenate([d_v2, c_v2], axis=1)
            # booleans are joined as int32 (Mosaic cannot concatenate
            # i1 vectors)
            enab = jnp.concatenate([det_en.astype(jnp.int32),
                                    crash_en.astype(jnp.int32)],
                                   axis=1) == 1

            # SAFE_MODELS' jsteps are elementwise, so they run on whole
            # [F, L] planes with the state words leading ([SW, F, L]):
            # 2D tiles, no vmap-made trailing axis of width SW
            stateB = jnp.stack([jnp.broadcast_to(state[:, swi:swi + 1],
                                                 (F, L))
                                for swi in range(SW)])
            ns, legal = model.jstep(stateB, aF, aV1, aV2)
            ns = jnp.broadcast_to(ns, (SW, F, L))
            valid = aliv & enab & legal

            wsum = jnp.sum(win, axis=1, keepdims=True)
            remaining = n_det - (p + wsum)               # [F, 1]
            # no select of booleans anywhere in the kernel: Mosaic
            # cannot lower one, so it is spelled as boolean algebra
            goal = valid & ((is_det_lane & (remaining <= 1))
                            | (~is_det_lane & (remaining <= 0)))
            v2r[:] = valid.astype(jnp.int32)
            g2r[:] = goal.astype(jnp.int32)
            nsr[:] = ns.astype(jnp.int32)

        def succ_compact(vmask, cap):
            """Compact the [F, L] valid mask to ``cap`` survivors in
            (f-major, lane-ascending) order and build their successor
            planes.  Returns (p2, win2, crash2, state2, svalid, total).
            Mirrors _succ_block + succ_one."""
            vf = _f32(vmask)
            c_row = jnp.sum(vf, axis=1, keepdims=True)   # [F, 1]
            # trilF[i, j] = (j <= i): cum = trilF @ c_row is the
            # INCLUSIVE prefix sum cum[i] = sum_{j<=i} c_row[j]
            trilF = _f32(_iota(F, 1, (F, F)) <= _iota(F, 0, (F, F)))
            cum = _mm(trilF, c_row)                      # [F, 1]
            o = cum - c_row                              # exclusive
            total = jnp.sum(vf).astype(jnp.int32)
            s_i = _iota(cap, 0, (cap, 1))
            oT, cT = _transpose_cols([o.astype(jnp.int32),
                                      c_row.astype(jnp.int32)])
            row_oh = _f32((oT <= s_i) & (s_i < oT + cT))
            q = _f32(s_i) - _mm(row_oh, o)               # [cap, 1]
            trilL = _f32(_iota(L, 0, (L, L)) <= _iota(L, 1, (L, L)))
            r = _mm(vf, trilL)                           # [F, L] ranks
            Rg = _mm(row_oh, r)                          # [cap, L]
            Vg = _mm(row_oh, vf)
            lane_oh = (Rg == q + 1) & (Vg > 0.5)         # [cap, L]
            svalid = s_i < total                         # [cap, 1]

            lane = jnp.sum(jnp.where(lane_oh, _iota(L, 1, (cap, L)), 0),
                           axis=1, keepdims=True)        # [cap, 1]
            p_src = _mm(row_oh, _f32(pc[:])).astype(jnp.int32)
            win_src = (_mm(row_oh, _f32(wc[:])) > 0.5)   # [cap, W] bool
            crash_src = (_mm(row_oh, _f32(cc[:])) > 0.5)
            state_src = _gather_i32(row_oh, stc[:])      # [cap, SW]

            lane_f = _f32(lane_oh)
            ns_cols = []
            for swi in range(SW):
                g = _gather_i32(row_oh * 1.0, nsr[swi])
                # row-gathered [cap, L] already int; select the lane
                ns_cols.append(jnp.sum(jnp.where(lane_oh, g, 0),
                                       axis=1, keepdims=True))
            ns_sel = jnp.concatenate(ns_cols, axis=1)    # [cap, SW]

            is_d = lane < W                              # [cap, 1]
            lwc = _iota(W, 1, (cap, W))
            win1 = win_src | (is_d & (lwc == lane))
            first_zero = jnp.min(jnp.where(~win1, lwc, W), axis=1,
                                 keepdims=True)          # = shift
            shift = first_zero
            # win2[s, l] = win1[s, l + shift_s]: a per-row dynamic shift
            # (0..W) as a barrel shifter — one exact 0/1 matmul by a
            # constant shift matrix per bit of the shift, selected per
            # row.  Lane slices at unaligned offsets, and a zero-width
            # one at shift W, are what Mosaic refuses or compiles slowly
            win2f = _f32(win1)
            k = 1
            while k <= W:
                shk = _f32(_iota(W, 0, (W, W)) == _iota(W, 1, (W, W)) + k)
                bit = jnp.bitwise_and(shift, k) != 0     # [cap, 1]
                win2f = jnp.where(bit, _mm(win2f, shk), win2f)
                k *= 2
            win2 = win2f > 0.5
            p2 = jnp.where(is_d, p_src + shift, p_src)
            w_out = (is_d & win2) | (~is_d & win_src)
            cloh = (lane - W) == _iota(NC, 1, (cap, NC))
            c_out = crash_src | (~is_d & cloh)
            return (p2, w_out.astype(jnp.int32), c_out.astype(jnp.int32),
                    ns_sel, svalid, total)

        def prune(pm, winm, crashm, statem, validm, M):
            """Exact all-pairs dominance over M rows; mirrors
            _allpairs_dominance (linearizable.py) on planes.  Masks
            are [M, 1] columns; the row forms come from one transpose
            of a stacked block (Mosaic reshapes [M, 1] -> [1, M] one
            element at a time)."""
            wsum = jnp.sum(winm, axis=1, keepdims=True)
            csum = jnp.sum(crashm, axis=1, keepdims=True)
            keys = [pm] + [statem[:, swi:swi + 1] for swi in range(SW)]
            cols = keys + [wsum, csum, validm.astype(jnp.int32)]
            rows = _transpose_cols(cols)
            r_wsum, r_csum, r_valid = rows[len(keys):]
            neq = jnp.zeros((M, M), jnp.int32)
            for col, row in zip(keys, rows):
                neq = neq + (col != row).astype(jnp.int32)
            wf = _f32(winm)
            cf_ = _f32(crashm)
            wcom = _mm(wf, wf.T)
            ccom = _mm(cf_, cf_.T)
            # |a| + |b| - 2|a & b| == 0  <=>  equal bit planes
            eq = (neq == 0) & ((_f32(wsum) + _f32(r_wsum) - 2.0 * wcom)
                               == 0)
            eq_cr = (_f32(csum) + _f32(r_csum) - 2.0 * ccom) == 0
            # sub[i, j]: cr_j subset of cr_i  <=>  |cr_j| - |inter| == 0
            sub = (_f32(r_csum) - ccom) == 0
            im = _iota(M, 0, (M, M))
            jm = _iota(M, 1, (M, M))
            dom = ((r_valid == 1) & eq
                   & ((sub & ~eq_cr) | (eq_cr & (jm < im))))
            return validm & ~jnp.any(dom, axis=1, keepdims=True)

        def compact_rows(kept, pm, winm, crashm, statem, M):
            """First-F kept rows, in order; returns planes + kept
            count.  ``kept`` is an [M, 1] column."""
            kf = _f32(kept)                              # [M, 1]
            trilM = _f32(_iota(M, 1, (M, M)) <= _iota(M, 0, (M, M)))
            rank = _mm(trilM, kf)                        # [M, 1] incl
            n_kept = jnp.sum(kf).astype(jnp.int32)
            r_rank = _transpose_cols(
                [jnp.where(kept, rank.astype(jnp.int32), 0)])[0]
            out_oh = _f32(r_rank == _iota(F, 0, (F, 1)) + 1)   # [F, M]
            p_n = _mm(out_oh, _f32(pm)).astype(jnp.int32)
            w_n = (_mm(out_oh, _f32(winm)) > 0.5).astype(jnp.int32)
            c_n = (_mm(out_oh, _f32(crashm)) > 0.5).astype(jnp.int32)
            s_n = _gather_i32(out_oh, statem)
            return p_n, w_n, c_n, s_n, n_kept

        def closure_round(_j, carry):
            @pl.when(st[_CLGO, 0] == 1)
            def _():
                if telemetry:
                    st[_TROUNDS, 0] = st[_TROUNDS, 0] + 1
                cvalid = (v2r[:] == 1) & ~is_det_lane
                p2, w2, c2, s2, svld, ntot = succ_compact(cvalid, F)
                st[_OVF, 0] = st[_OVF, 0] | jnp.where(ntot > F, 1, 0)
                count = st[_CNT, 0]
                aliv = _iota(F, 0, (F, 1)) < count
                pm = jnp.concatenate([pc[:], p2], axis=0)
                wm = jnp.concatenate([wc[:], w2], axis=0)
                cm = jnp.concatenate([cc[:], c2], axis=0)
                sm = jnp.concatenate([stc[:], s2], axis=0)
                vm = jnp.concatenate([aliv.astype(jnp.int32),
                                      svld.astype(jnp.int32)],
                                     axis=0) == 1
                kept = prune(pm, wm, cm, sm, vm, 2 * F)
                p_n, w_n, c_n, s_n, nk = compact_rows(
                    kept, pm, wm, cm, sm, 2 * F)
                st[_OVF, 0] = st[_OVF, 0] | jnp.where(nk > F, 1, 0)
                progress = jnp.any(
                    kept & (_iota(2 * F, 0, (2 * F, 1))
                            >= F))
                pc[:] = p_n
                wc[:] = w_n
                cc[:] = c_n
                stc[:] = s_n
                st[_CNT, 0] = jnp.minimum(nk, F)
                mask_phase()
                st[_FOUND, 0] = st[_FOUND, 0] | jnp.where(jnp.any(g2r[:] == 1), 1, 0)
                st[_CLGO, 0] = jnp.where(progress, 1, 0)
            return carry

        def level(_i, carry):
            @pl.when(st[_RUN, 0] == 1)
            def _():
                # entry snapshot for the uncommitted-overflow revert
                ps[:] = pc[:]
                ws[:] = wc[:]
                cs[:] = cc[:]
                sts[:] = stc[:]
                st[_CNT0, 0] = st[_CNT, 0]
                st[_CFG0, 0] = st[_CFG, 0]
                st[_MD0, 0] = st[_MD, 0]
                st[_OVF0, 0] = st[_OVF, 0]
                if telemetry:
                    st[_TROUNDS, 0] = 0

                mask_phase()
                found0 = jnp.any(g2r[:] == 1)
                st[_FOUND, 0] = jnp.where(found0, 1, 0)
                crash_any = jnp.any((v2r[:] == 1) & ~is_det_lane)
                st[_CLGO, 0] = jnp.where(crash_any, 1, 0)
                lax.fori_loop(0, n_crash + 1, closure_round, 0)
                # exit-by-cap while still adding rows: not proven
                # closed — degrade like an overflow
                st[_OVF, 0] = st[_OVF, 0] | st[_CLGO, 0]

                # determinate expansion
                dvalid = (v2r[:] == 1) & is_det_lane
                p2, w2, c2, s2, svld, ntot = succ_compact(dvalid, SCAP)
                st[_OVF, 0] = st[_OVF, 0] | jnp.where(ntot > SCAP, 1, 0)
                kept = prune(p2, w2, c2, s2, svld, SCAP)
                p_n, w_n, c_n, s_n, nk = compact_rows(
                    kept, p2, w2, c2, s2, SCAP)
                st[_OVF, 0] = st[_OVF, 0] | jnp.where(nk > F, 1, 0)

                count = st[_CNT, 0]
                if telemetry:
                    st[_TOCC, 0] = count  # post-closure occupancy
                aliv = _iota(F, 0, (F, 1)) < count
                st[_CFG, 0] = st[_CFG, 0] + count
                st[_MD, 0] = jnp.maximum(
                    st[_MD, 0], jnp.max(jnp.where(aliv, pc[:], 0)))
                found = st[_FOUND, 0] == 1
                st[_STA, 0] = jnp.where(found, 2, st[_STA, 0])
                new_ovf = (st[_OVF, 0] == 1) & (st[_OVF0, 0] == 0)
                revert = (bail == 1) & new_ovf & ~found
                pc[:] = jnp.where(revert, ps[:], p_n)
                wc[:] = jnp.where(revert, ws[:], w_n)
                cc[:] = jnp.where(revert, cs[:], c_n)
                stc[:] = jnp.where(revert, sts[:], s_n)
                st[_CNT, 0] = jnp.where(revert, st[_CNT0, 0],
                                        jnp.minimum(nk, F))
                st[_CFG, 0] = jnp.where(revert, st[_CFG0, 0],
                                        st[_CFG, 0])
                st[_MD, 0] = jnp.where(revert, st[_MD0, 0], st[_MD, 0])
                st[_RUN, 0] = jnp.where(
                    (st[_STA, 0] == -1) & (st[_CNT, 0] > 0)
                    & (st[_CFG, 0] < budget)
                    & ~((bail == 1) & (st[_OVF, 0] == 1)), 1, 0)
                if telemetry:
                    # one aux row per level, written as a one-hot
                    # elementwise add on the [TELE_ROWS, TELE_COLS]
                    # plane (no dynamic stores).  mask_killed (col 2)
                    # and dedup_folds (col 3) are structurally 0 —
                    # pallas-eligible searches carry no reductions.
                    idx = jnp.minimum(st[_TLVL, 0], TELE_ROWS - 1)
                    roh = (_iota(TELE_ROWS, 0,
                                 (TELE_ROWS, TELE_COLS)) == idx)
                    colI = _iota(TELE_COLS, 1, (TELE_ROWS, TELE_COLS))
                    expd = jnp.sum(v2r[:]).astype(jnp.int32)
                    vals = (st[_TOCC, 0] * (colI == C_OCC)
                            + expd * (colI == C_EXP)
                            + st[_TROUNDS, 0] * (colI == C_ROUNDS)
                            + st[_CNT, 0] * (colI == C_NEXT)
                            + jnp.where((st[_OVF, 0] == 1)
                                        & (st[_OVF0, 0] == 0), 1, 0)
                            * (colI == C_OVF)
                            + st[_FOUND, 0] * (colI == C_GOAL))
                    tele_out[:] = tele_out[:] + jnp.where(
                        roh, vals.astype(jnp.int32), 0)
                    st[_TLVL, 0] = st[_TLVL, 0] + 1
            return carry

        lax.fori_loop(0, lvl_cap, level, 0)

        p_out[:] = pc[:]
        win_out[:] = wc[:]
        crash_out[:] = cc[:]
        state_out[:] = stc[:]
        for i, slot in ((0, _CNT), (1, _STA), (2, _CFG), (3, _MD),
                        (4, _OVF)):
            scal_out[i, 0] = st[slot, 0]

    vmem = {"memory_space": pltpu.VMEM}
    smem = {"memory_space": pltpu.SMEM}

    def _scratch(shape, dtype=jnp.int32):
        return pltpu.VMEM(shape, dtype)

    out_specs = [pl.BlockSpec(**vmem)] * 4 + [pl.BlockSpec(**smem)]
    out_shape = [
        jax.ShapeDtypeStruct((F, 1), jnp.int32),
        jax.ShapeDtypeStruct((F, W), jnp.int32),
        jax.ShapeDtypeStruct((F, NC), jnp.int32),
        jax.ShapeDtypeStruct((F, SW), jnp.int32),
        jax.ShapeDtypeStruct((5, 1), jnp.int32),
    ]
    if telemetry:
        out_specs.append(pl.BlockSpec(**vmem))
        out_shape.append(
            jax.ShapeDtypeStruct((TELE_ROWS, TELE_COLS), jnp.int32))
    call = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(**smem)] + [pl.BlockSpec(**vmem)] * 14,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            _scratch((F, 1)), _scratch((F, W)), _scratch((F, NC)),
            _scratch((F, SW)),
            _scratch((F, 1)), _scratch((F, W)), _scratch((F, NC)),
            _scratch((F, SW)),
            _scratch((F, L)), _scratch((F, L)), _scratch((SW, F, L)),
            pltpu.SMEM((16, 1), jnp.int32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
    )

    def step(det_f, det_v1, det_v2, det_inv, det_ret, sfx_min,
             crash_f, crash_v1, crash_v2, crash_inv, det_mpred,
             det_cpredw, crash_mpred, crash_cpredw, dead_from,
             n_det, n_crash, dead_lo, dead_tok,
             budget, lvl_cap, bail,
             frontier, count, status, configs, max_depth, ovf):
        # det_mpred..dead_tok: phase-2 reduction planes, part of the
        # shared step signature; unmasked/undeduped by eligibility, so
        # they are deliberately unused here
        del det_mpred, det_cpredw, crash_mpred, crash_cpredw
        del dead_from, dead_lo, dead_tok
        # ---- XLA boundary: unpack packed words to planes ----------
        win = ((frontier[:, 1 + w_word] >> w_bit) & 1).astype(jnp.int32)
        crash = ((frontier[:, 1 + WW + c_word] >> c_bit)
                 & 1).astype(jnp.int32)
        p = frontier[:, 0:1]
        state = frontier[:, 1 + WW + CW:]
        scal = jnp.stack([
            count.astype(jnp.int32), status.astype(jnp.int32),
            configs.astype(jnp.int32), max_depth.astype(jnp.int32),
            ovf.astype(jnp.int32), n_det, n_crash, budget, lvl_cap,
            bail.astype(jnp.int32), jnp.int32(0), jnp.int32(0),
        ]).reshape(12, 1)
        clamp = functools.partial(jnp.minimum, CLAMP_INF)

        def row(t):
            # [1, ND2]: lanes past the table's end repeat its last
            # entry (det lanes there are masked by n_det; the suffix
            # minimum past n_det is the empty suffix's)
            return jnp.pad(t, (0, ND2 - t.shape[0]), mode="edge")[None, :]

        outs = call(
            scal,
            row(det_f), row(det_v1), row(det_v2),
            row(clamp(det_inv)), row(clamp(det_ret)),
            row(clamp(sfx_min)),
            crash_f[None, :], crash_v1[None, :], crash_v2[None, :],
            clamp(crash_inv)[None, :],
            p, win, crash, state)
        if telemetry:
            p_o, win_o, crash_o, state_o, scal_o, tele_o = outs
        else:
            p_o, win_o, crash_o, state_o, scal_o = outs
        # ---- pack planes back to words ----------------------------
        wshift = jnp.asarray(w_bit, jnp.int32)
        cshift = jnp.asarray(c_bit, jnp.int32)
        # disjoint bit values sum to their OR (int32 addition wraps, so
        # bit 31 round-trips through its negative two's-complement value)
        win_words = jnp.stack(
            [(win_o[:, wi * 32:min((wi + 1) * 32, W)]
              << wshift[wi * 32:min((wi + 1) * 32, W)]).sum(axis=1)
             for wi in range(WW)], axis=1)
        crash_words = jnp.stack(
            [(crash_o[:, wi * 32:min((wi + 1) * 32, NC)]
              << cshift[wi * 32:min((wi + 1) * 32, NC)]).sum(axis=1)
             for wi in range(CW)], axis=1)
        frontier_o = jnp.concatenate(
            [p_o, win_words, crash_words, state_o], axis=1)
        out = (frontier_o, scal_o[0, 0], scal_o[1, 0], scal_o[2, 0],
               scal_o[3, 0], scal_o[4, 0].astype(bool))
        if telemetry:
            out = out + (tele_o,)
        return out

    return step
