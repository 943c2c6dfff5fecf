"""entry points: host microseconds per configuration the certification
searched: the seconds of the `linearizable.certify` spans that begin in
the window, over the sum of their `configs`; None where there is
none."""


def read(ctx):
    t0, t1 = ctx["window"]
    spans = [s for s in ctx["spans"]
             if s["name"] == "linearizable.certify" and t0 <= s["t0"] < t1
             and s["args"].get("configs")]
    configs = sum(s["args"]["configs"] for s in spans)
    if not configs:
        return None
    return 1e6 * sum(s["t1"] - s["t0"] for s in spans) / configs
