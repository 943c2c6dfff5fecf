"""kernels: configurations a level of the search passes to the next, on
average: the `next_count` of the `device.level` spans inside the
`device.slice` spans of the window whose `state_words` (the words of a
packed configuration) is above 1, over the levels those slices ran
(their `levels_run`: a slice of more than 128 levels folds the rest
into its last level span, so the spans undercount the levels).  None
where no slice carries `state_words` (a program older than that arg)."""


def read(ctx):
    t0, t1 = ctx["window"]
    slices = [s for s in ctx["spans"]
              if s["name"] == "device.slice" and t0 <= s["t0"] < t1
              and (s["args"].get("state_words") or 0) > 1]
    levels = sum(s["args"].get("levels_run", 0) for s in slices)
    if not levels:
        return None
    passed = sum(x["args"].get("next_count", 0) for x in ctx["spans"]
                 if x["name"] == "device.level"
                 and any(s["t0"] <= x["t0"] <= s["t1"] for s in slices))
    return passed / levels
