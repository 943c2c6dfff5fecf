"""The system under test: the program's checker, built as a user builds
it, fed the generator's histories.  The only module of the benchmark
that imports `jepsen_tpu`."""

from __future__ import annotations

import sys

from .spec import ROOT

if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def build(config):
    """The checker a suite builds for this deployment: the one-line
    `Linearizable(<model>(...), algorithm=...)` swap, the program's
    model named by the configuration's ``checker`` entry and given the
    configuration's initial value (``init``, which every configuration
    states; null is the model's own default), every other knob at its
    library default; wrapped in `independent.checker` for a test over
    independent keys."""
    from jepsen_tpu import independent, models
    from jepsen_tpu.checker.linearizable import Linearizable

    make = getattr(models, config["checker"]["model"])
    model = make() if config["init"] is None else make(config["init"])
    chk = Linearizable(model, algorithm=config["checker"]["algorithm"])
    if config["layout"] == "independent":
        chk = independent.checker(chk)
    return chk, model


def history(events):
    """Generator events as the program's ops; keyed values as
    `independent` tuples."""
    from jepsen_tpu.history import Op
    from jepsen_tpu.independent import KV

    return [Op(e[0], e[1], e[2], e[-1] if len(e) == 4 or e[0] == "nemesis"
               else KV(e[3], e[4]))
            for e in events]


def verdicts(result, layout):
    """{key: answer}, key None for a single history; an answer holds
    ``valid``, and the certificate: ``linearization`` (row indices) of a
    valid verdict, ``max_depth`` and ``final_paths`` (lists of row
    indices) of an invalid one.  A key the program gave no verdict for
    is missing."""
    per = (result.get("results") or {}) if layout == "independent" \
        else {None: result}
    return {k: {"valid": r.get("valid"),
                "linearization": r.get("linearization"),
                "max_depth": r.get("max_depth"),
                "final_paths": [p.get("linearized")
                                for p in r.get("final_paths") or []]}
            for k, r in per.items()}


def counters():
    """The program's device-search counters, by name."""
    from jepsen_tpu.obs import metrics

    return {name: metrics.REGISTRY.get(name).total()
            for name in ("jtpu_search_levels_total",)}


def tracing(on):
    """Turn the program's span recorder on (traced runs) or off, and
    empty it."""
    from jepsen_tpu import obs
    from jepsen_tpu.obs import trace

    obs.enable(on)
    trace.recorder(None).clear()


def spans():
    """The program's finished spans: name, args, and start/end on
    `time.perf_counter()`'s clock."""
    from jepsen_tpu.obs import trace

    out = []
    for s in trace.recorder(None).spans():
        t0 = trace._EPOCH + s["ts"] / 1e6
        out.append({"name": s["name"], "t0": t0, "t1": t0 + s["dur"] / 1e6,
                    "args": s["args"]})
    return out
