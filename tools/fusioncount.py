"""Op-count proxy for the on-chip per-level floor.

docs/perf-notes.md: the per-level floor at narrow widths tracks the
COUNT of executable computations in the compiled level body (each pays
a fixed launch overhead on a TPU), not the data volume.  This tool compiles the single-device search
kernel at a given width on the CPU backend, finds the LEVEL-LOOP body
computation in the optimized HLO, and prints its executable-op
histogram (fusions + non-trivial ops; tuple plumbing excluded) plus
every nested loop — the metric every depth-axis optimization is judged
by before a chip run can time it for real.

Usage: JAX_PLATFORMS=cpu python tools/fusioncount.py [--tier mutex2k]
       [--widths 16,64,256]
"""

import argparse
import collections
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: instructions that are data plumbing, not executable work
_CHEAP = {"tuple", "get-tuple-element", "parameter", "constant",
          "bitcast"}

#: one HLO instruction: `%name = <type> kind(...)` where <type> may be
#: a tuple `(s32[16]{0}, pred[])` (spaces inside — `\S+` never spans
#: it, which silently zeroed the while/fusion counts in the first
#: version of this tool)
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\([^=]*?\)|\S+)\s+"
    r"([\w\-]+)\(")


def split_computations(txt: str) -> dict[str, list[str]]:
    """computation name -> its instruction lines."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in txt.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(.*\{$",
                     line.strip())
        if m:
            cur = m.group(1)
            comps[cur] = []
        elif cur is not None and line.strip() == "}":
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps


def instr_kinds(lines: list[str]) -> collections.Counter:
    c: collections.Counter = collections.Counter()
    for ln in lines:
        m = _INSTR.match(ln)
        if m:
            c[m.group(1)] += 1
    return c


def body_stats(comps: dict, name: str, depth: int = 0, max_depth: int = 3):
    """Executable-op histogram of one computation + its nested whiles."""
    kinds = instr_kinds(comps[name])
    execu = sum(v for k, v in kinds.items() if k not in _CHEAP)
    nested = []
    if depth < max_depth:
        for ln in comps[name]:
            m = re.search(r"\bwhile\(.*?body=(%[\w.\-]+)", ln)
            if m and m.group(1) in comps:
                tc = re.search(r'known_trip_count..\{.n.:.(\d+)', ln)
                nested.append((m.group(1),
                               int(tc.group(1)) if tc else None,
                               body_stats(comps, m.group(1),
                                          depth + 1, max_depth)))
    return {"kinds": dict(kinds), "exec": execu, "nested": nested}


def find_level_body(comps: dict) -> str | None:
    """The outermost while body: the computation that contains the most
    instructions among bodies referenced by a while whose op_name ends
    in 'while' (the level loop)."""
    best = None
    for name, lines in comps.items():
        for ln in lines:
            m = re.search(r"\bwhile\(.*?body=(%[\w.\-]+)", ln)
            if not m or m.group(1) not in comps:
                continue
            op = re.search(r'op_name="([^"]*)"', ln)
            # the level loop is the while whose op_name has exactly one
            # /while segment (nested closure/searchsorted whiles have
            # deeper paths)
            if op and op.group(1).count("while") == 1:
                cand = m.group(1)
                if best is None or (len(comps[cand])
                                    > len(comps[best])):
                    best = cand
    return best


def _print_stats(label, st, indent="  "):
    top = sorted(((k, v) for k, v in st["kinds"].items()
                  if k not in _CHEAP), key=lambda kv: -kv[1])
    print(f"{indent}{label}: exec={st['exec']} "
          f"{dict(top[:8])}")
    for bname, trips, sub in st["nested"]:
        _print_stats(f"while body={bname} trips={trips}", sub,
                     indent + "  ")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", default="mutex2k")
    ap.add_argument("--widths", default="16,64,256")
    ap.add_argument("--dump", help="write full HLO text per width here")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp

    import bench
    from jepsen_tpu.checker import linearizable as lin

    seq, model = bench.make_seq(args.tier)
    es = lin.encode_search(seq)
    for f in (int(w) for w in args.widths.split(",")):
        dims = lin.choose_dims(es, model, frontier=f)
        esp = lin.pad_search(es, dims.n_det_pad, dims.n_crash_pad)
        fn = jax.jit(lin.build_search_step_fn(model, dims))
        carry = lin._init_carry(dims, model)
        a = (jnp.asarray(esp.det_f), jnp.asarray(esp.det_v1),
             jnp.asarray(esp.det_v2), jnp.asarray(esp.det_inv),
             jnp.asarray(esp.det_ret), jnp.asarray(esp.suffix_min_ret),
             jnp.asarray(esp.crash_f), jnp.asarray(esp.crash_v1),
             jnp.asarray(esp.crash_v2), jnp.asarray(esp.crash_inv),
             jnp.int32(es.n_det), jnp.int32(es.n_crash),
             jnp.int32(10 ** 9), jnp.int32(64), jnp.bool_(True))
        txt = fn.lower(*a, *carry).compile().as_text()
        comps = split_computations(txt)
        body = find_level_body(comps)
        print(f"F={f}: computations={len(comps)}")
        if body is None:
            print("  level-loop body not found")
        else:
            _print_stats(f"LEVEL body {body}", body_stats(comps, body))
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump,
                                   f"hlo_{args.tier}_F{f}.txt"),
                      "w") as fh:
                fh.write(txt)


if __name__ == "__main__":
    main()
