"""The controls of the correctness check: the plain reference put in the
program's place, breaking one guarantee the configurations state.  A
run with a cell's control in the program's place has to come out not
correct.  The cell's traffic picks its control:

* where it makes histories invalid (``stale_share`` above 0): the
  model's weaker guarantee, its ``step`` with ``control=True`` (for the
  CAS register, a read may return any value the register has held:
  etcd's guarantee without quorum reads).  It answers "valid" where the
  model's own corruption made a history invalid.
* where every history is valid: a crashed (info) op taken as one that
  never took effect, the shortcut that would spare a search every
  indeterminate op.  It answers "invalid" where a crashed op did take
  effect and a later op saw it; any weaker guarantee would answer the
  truth of an all-valid cell.

`python3 benchmark/run.py ... --control 1` runs it at a cell's own
size; the tests run it at a small one."""

from __future__ import annotations

from . import reference


class Checker:
    def __init__(self, config, model, traffic):
        self.config, self.model = config, model
        # the stale cells step the weaker model; the all-valid ones drop
        # every crashed op
        self.weaker = bool(traffic.get("stale_share", 0))

    def _valid(self, events):
        rows = reference.rows_of(events)
        if not self.weaker:
            rows = [op for op in rows if op["type"] == "ok"]
        return reference.check(self.model, rows, self.config["init"],
                               control=self.weaker)[0]

    def check(self, _test, events):
        if self.config["layout"] == "independent":
            return {"results": {k: {"valid": self._valid(e)} for k, e in
                                reference.split_keys(events).items()}}
        return {"valid": self._valid(events)}


class Sut:
    """The interface of `core/sut.py`, with the control of ``model`` that
    the cell's ``traffic`` picks behind it."""

    def __init__(self, model, traffic):
        self.model, self.traffic = model, traffic

    def build(self, config):
        return Checker(config, self.model, self.traffic), None

    @staticmethod
    def history(events):
        return events

    @staticmethod
    def verdicts(result, layout):
        from . import sut

        return sut.verdicts(result, layout)

    @staticmethod
    def counters():
        return {}

    @staticmethod
    def tracing(_on):
        pass

    @staticmethod
    def spans():
        return []
