"""Suite protocol lint (jepsen_tpu/analyze/suites.py) — the CI gate.

``test_bundled_suites_have_no_protocol_errors`` is the tier-1 guard: a
new suite cannot merge with an ERROR-severity protocol violation (broad
except converting crashes to determinate completions, invoke paths that
return None, nemesis completions that aren't :info).  The rest pins the
rules themselves on fixture sources, and regression-tests the defects
the lint actually found in the bundled suites.
"""

import json
import os
import subprocess
import sys
import urllib.error

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from jepsen_tpu.analyze.suites import (  # noqa: E402
    SUITE_CODES,
    lint_live_source,
    lint_paths,
    lint_source,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def codes(diags, severity=None):
    return {d.code for d in diags
            if severity is None or d.severity == severity}


# ---------------------------------------------------------------------------
# the CI gate: bundled suites must be protocol-clean
# ---------------------------------------------------------------------------


def test_bundled_suites_have_no_protocol_errors():
    findings = lint_paths()
    errors = [(f, d) for f, ds in findings.items() for d in ds
              if d.severity == "error"]
    assert errors == [], "suite protocol errors:\n" + "\n".join(
        f"  {d.message}" for _f, d in errors)


def test_lint_suites_cli_json():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_suites.py"),
         "--json"],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["errors"] == 0
    assert set(payload) == {"errors", "warnings", "files"}


def test_lint_suites_cli_flags_errors(tmp_path):
    bad = tmp_path / "bad_suite.py"
    bad.write_text(
        "class FooClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        try:\n"
        "            return replace(op, type='ok')\n"
        "        except Exception:\n"
        "            return replace(op, type='ok')\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_suites.py"),
         str(bad)],
        capture_output=True, text=True, cwd=REPO)
    assert out.returncode == 1
    assert "S002" in out.stdout


# ---------------------------------------------------------------------------
# the rules, on fixture sources
# ---------------------------------------------------------------------------


def test_s001_invoke_returns_none_and_falls_off():
    src = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        if op.f == 'read':\n"
        "            return None\n")
    diags = lint_source(src, "fix.py")
    assert codes(diags, "error") == {"S001"}
    assert len(diags) == 2  # the None return AND the fall-through


def test_s001_return_op_unchanged():
    src = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        return op\n")
    assert "S001" in codes(lint_source(src, "f.py"), "error")
    # reassigned op is a completion — not flagged
    src_ok = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        op = replace(op, type='ok')\n"
        "        return op\n")
    assert lint_source(src_ok, "f.py") == []


def test_s001_clean_shapes_accepted():
    src = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        try:\n"
        "            if op.f == 'read':\n"
        "                return replace(op, type='ok', value=1)\n"
        "            raise ValueError(op.f)\n"
        "        except OSError as e:\n"
        "            return replace(op, type='info', error=str(e))\n")
    assert lint_source(src, "f.py") == []


def test_s002_broad_except_to_ok():
    src = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        try:\n"
        "            return replace(op, type='ok')\n"
        "        except Exception:\n"
        "            return replace(op, type='ok')\n")
    assert "S002" in codes(lint_source(src, "f.py"), "error")


def test_s003_broad_except_unconditional_fail():
    src = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        try:\n"
        "            return replace(op, type='ok')\n"
        "        except Exception as e:\n"
        "            return replace(op, type='fail', error=str(e))\n")
    assert "S003" in codes(lint_source(src, "f.py"), "error")


def test_s003_guarded_or_conditional_fail_is_clean():
    # the idiomatic forms stay clean: a type conditioned on op.f, a
    # fail return guarded by an exception test with re-raise
    src = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        try:\n"
        "            return replace(op, type='ok')\n"
        "        except Exception as e:\n"
        "            if 'conflict' in str(e):\n"
        "                return replace(op, type='fail')\n"
        "            raise\n")
    assert lint_source(src, "f.py") == []
    src2 = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        try:\n"
        "            return replace(op, type='ok')\n"
        "        except Exception as e:\n"
        "            return replace(op, type='fail' if op.f == 'read'"
        " else 'info', error=str(e))\n")
    assert lint_source(src2, "f.py") == []


def test_s004_db_pairing():
    src = (
        "class FooDB(db_mod.DB):\n"
        "    def setup(self, test, node):\n"
        "        pass\n")
    diags = lint_source(src, "f.py")
    assert codes(diags) == {"S004"}
    assert all(d.severity == "warning" for d in diags)


def test_s005_nemesis_completion_type():
    src = (
        "class FooNemesis(nemesis_mod.Nemesis):\n"
        "    def invoke(self, test, op):\n"
        "        return replace(op, type='ok')\n")
    assert "S005" in codes(lint_source(src, "f.py"), "error")
    src_ok = src.replace("'ok'", "'info'")
    assert lint_source(src_ok, "f.py") == []


def test_suppression_comment():
    src = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        try:\n"
        "            return replace(op, type='ok')\n"
        "        except Exception as e:\n"
        "            return replace(op, type='fail')  # suite-lint: ok\n")
    assert lint_source(src, "f.py") == []


def test_codes_documented():
    for code in ("S001", "S002", "S003", "S004", "S005",
                 "B001", "B002", "B003"):
        assert code in SUITE_CODES


# ---------------------------------------------------------------------------
# B-codes: live backend protocol (jepsen_tpu/live/)
# ---------------------------------------------------------------------------


def test_b001_concrete_backend_missing_protocol_member():
    src = (
        "class BrokenBackend(LiveBackend):\n"
        "    name = 'broken'\n"
        "    def workload(self, opts):\n"
        "        return {}\n")
    assert "B001" in codes(lint_live_source(src, "f.py"), "error")


def test_b001_abstract_intermediate_is_exempt():
    # the replicated consensus core pattern: no `name`, protocol left
    # to concrete families — and the family inheriting through it is
    # clean when the chain provides everything
    src = (
        "class ConsensusBackend(LiveBackend):\n"
        "    def health_check(self, test, node):\n"
        "        pass\n"
        "class FamBackend(ConsensusBackend):\n"
        "    name = 'fam'\n"
        "    def server_argv(self, test, node):\n"
        "        return []\n"
        "    def workload(self, opts):\n"
        "        return {}\n")
    assert lint_live_source(src, "f.py") == []


def test_b001_annotated_name_and_async_members_recognized():
    # review regression: `name: str = 'fam'` (AnnAssign) and async
    # protocol members must count as provided
    src = (
        "class FamBackend(LiveBackend):\n"
        "    name: str = 'fam'\n"
        "    def server_argv(self, test, node):\n"
        "        return []\n"
        "    async def workload(self, opts):\n"
        "        return {}\n")
    assert lint_live_source(src, "f.py") == []
    # a bare annotation with no value is NOT a name assignment
    src2 = (
        "class ShyBackend(LiveBackend):\n"
        "    name: str\n"
        "    def server_argv(self, test, node):\n"
        "        return []\n"
        "    def workload(self, opts):\n"
        "        return {}\n")
    assert "B001" in codes(lint_live_source(src2, "f.py"), "error")


def test_b001_unnamed_but_complete_backend_flagged():
    src = (
        "class ShyBackend(LiveBackend):\n"
        "    def server_argv(self, test, node):\n"
        "        return []\n"
        "    def workload(self, opts):\n"
        "        return {}\n")
    diags = lint_live_source(src, "f.py")
    assert "B001" in codes(diags, "error")
    assert "name" in diags[0].message


def test_b002_live_helper_swallows_crash_to_fail():
    src = (
        "class Shim:\n"
        "    def fetch(self, op):\n"
        "        try:\n"
        "            return do(op)\n"
        "        except Exception:\n"
        "            return replace(op, type='fail')\n")
    assert "B002" in codes(lint_live_source(src, "f.py"), "error")
    # a guarded / re-raising handler stays clean
    src_ok = src.replace("            return replace(op, type='fail')\n",
                         "            if op.f == 'read':\n"
                         "                return replace(op, "
                         "type='fail')\n"
                         "            raise\n")
    assert lint_live_source(src_ok, "f.py") == []


def test_b002_does_not_double_report_client_invoke():
    # *Client.invoke is S003's beat (lint_source); the live lint must
    # not duplicate the finding
    src = (
        "class FooClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        try:\n"
        "            return replace(op, type='ok')\n"
        "        except Exception:\n"
        "            return replace(op, type='fail')\n")
    assert "B002" not in codes(lint_live_source(src, "f.py"))
    assert "S003" in codes(lint_source(src, "f.py"), "error")


def test_b003_rename_without_fsync():
    src = (
        "import os\n"
        "def save(path, data):\n"
        "    tmp = path + '.tmp'\n"
        "    with open(tmp, 'w') as f:\n"
        "        f.write(data)\n"
        "    os.replace(tmp, path)\n")
    assert "B003" in codes(lint_live_source(src, "f.py"), "error")
    src_ok = (
        "import os\n"
        "def save(path, data):\n"
        "    tmp = path + '.tmp'\n"
        "    with open(tmp, 'w') as f:\n"
        "        f.write(data)\n"
        "        f.flush()\n"
        "        os.fsync(f.fileno())\n"
        "    os.replace(tmp, path)\n")
    assert lint_live_source(src_ok, "f.py") == []
    # read-only opens next to a rename are not journal writes
    src_ro = (
        "import os\n"
        "def rotate(path):\n"
        "    with open(path, 'r') as f:\n"
        "        f.read()\n"
        "    os.rename(path, path + '.old')\n")
    assert lint_live_source(src_ro, "f.py") == []


def test_bundled_live_backends_are_clean():
    findings = lint_paths([os.path.join(REPO, "jepsen_tpu", "live")])
    errors = [d for ds in findings.values() for d in ds
              if d.severity == "error"]
    assert errors == [], "\n".join(d.message for d in errors)


# ---------------------------------------------------------------------------
# regression tests for the defects the lint found (satellite 2)
# ---------------------------------------------------------------------------


def test_chronos_crashed_addjob_is_indeterminate(monkeypatch):
    """chronos.py used to convert EVERY invoke crash to :fail — but a
    crashed add-job POST may have been applied, and a silently-scheduled
    job would then run without the checker expecting it.  Crashed
    add-jobs must complete :info; crashed reads (effect-free) stay
    :fail."""
    from jepsen_tpu.history import Op
    from jepsen_tpu.suites import chronos

    def boom(*a, **kw):
        raise urllib.error.URLError("connection refused")

    monkeypatch.setattr(chronos.urllib.request, "urlopen", boom)
    client = chronos.ChronosClient(node="n1")
    job = {"name": "j1", "start": 10.0, "count": 5, "interval": 60,
           "epsilon": 15, "duration": 5}
    out = client.invoke({}, Op(process=0, type="invoke", f="add-job",
                               value=job))
    assert out.type == "info"

    def read_boom(_test):
        raise OSError("ssh down")

    monkeypatch.setattr(chronos, "read_runs", read_boom)
    out = client.invoke({}, Op(process=0, type="invoke", f="read",
                               value=None))
    assert out.type == "fail"


def test_robustirc_close_deletes_server_session():
    """robustirc's SetClient opened a server-side session per open()
    and never deleted it — the worker reopens clients after every
    crash, so sessions accumulated on the server for the whole run.
    close() must issue the DELETE (and survive a dead server)."""
    from jepsen_tpu.suites import robustirc

    calls = []

    class FakeSession:
        def quit(self, message="x"):
            calls.append("quit")

    c = robustirc.SetClient("n1")
    c.session = FakeSession()
    c.close({})
    assert calls == ["quit"]
    assert c.session is None

    class DeadSession:
        def quit(self, message="x"):
            raise OSError("server gone")

    c2 = robustirc.SetClient("n1")
    c2.session = DeadSession()
    c2.close({})  # must not raise
    assert c2.session is None


def test_robustirc_session_quit_issues_delete(monkeypatch):
    from jepsen_tpu.suites import robustirc

    reqs = []

    class R:
        def __init__(self):
            self.fp = None

        def read(self):
            return b"{}"

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def close(self):
            pass

    def fake_urlopen(req, timeout=None, context=None):
        reqs.append((req.get_method(), req.full_url))
        return R()

    monkeypatch.setattr(robustirc.urllib.request, "urlopen",
                        fake_urlopen)
    monkeypatch.setattr(
        robustirc.IRCSession, "__init__",
        lambda self, node, timeout=10.0: (
            setattr(self, "node", str(node)),
            setattr(self, "timeout", timeout),
            setattr(self, "ctx", None),
            setattr(self, "session_id", "sess42"),
            setattr(self, "session_auth", "auth"),
        ) and None)
    s = robustirc.IRCSession("n1")
    s.quit()
    assert reqs and reqs[-1][0] == "DELETE"
    assert "/robustirc/v1/sess42" in reqs[-1][1]


@pytest.mark.parametrize("fname", ["chronos.py", "robustirc.py"])
def test_fixed_suites_stay_clean(fname):
    findings = lint_paths([os.path.join(
        REPO, "jepsen_tpu", "suites", fname)])
    errors = [d for ds in findings.values() for d in ds
              if d.severity == "error"]
    assert errors == []


# ---------------------------------------------------------------------------
# tools/lint_suites.py --json exit-code coverage (B fixtures): the CLI
# contract CI and scripts depend on — 1 on any error-severity finding,
# 0 on warning-only/clean, with the finding visible in the JSON payload
# ---------------------------------------------------------------------------


def _run_lint_json(*paths):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint_suites.py"),
         "--json", *map(str, paths)],
        capture_output=True, text=True, cwd=REPO)
    return out.returncode, json.loads(out.stdout)


def test_lint_suites_json_exit_1_on_b_code_fixture(tmp_path):
    live = tmp_path / "live"
    live.mkdir()
    bad = live / "bad_backend.py"
    bad.write_text(
        "import os\n"
        "def journal(path, line):\n"
        "    tmp = path + '.tmp'\n"
        "    with open(tmp, 'w') as f:\n"
        "        f.write(line)\n"
        "    os.replace(tmp, path)\n")
    rc, payload = _run_lint_json(bad)
    assert rc == 1
    assert payload["errors"] >= 1
    found = {d["code"] for ds in payload["files"].values() for d in ds}
    assert "B003" in found


def test_lint_suites_json_exit_0_on_clean_live_fixture(tmp_path):
    live = tmp_path / "live"
    live.mkdir()
    clean = live / "ok_backend.py"
    clean.write_text(
        "import os\n"
        "def journal(path, line):\n"
        "    tmp = path + '.tmp'\n"
        "    with open(tmp, 'w') as f:\n"
        "        f.write(line)\n"
        "        f.flush()\n"
        "        os.fsync(f.fileno())\n"
        "    os.replace(tmp, path)\n")
    rc, payload = _run_lint_json(clean)
    assert rc == 0
    assert payload["errors"] == 0


def test_lint_suites_json_exit_1_on_b002_fixture(tmp_path):
    live = tmp_path / "live"
    live.mkdir()
    bad = live / "swallow_backend.py"
    bad.write_text(
        "from dataclasses import replace\n"
        "def probe(op):\n"
        "    try:\n"
        "        return do(op)\n"
        "    except Exception:\n"
        "        return replace(op, type='fail')\n")
    rc, payload = _run_lint_json(bad)
    assert rc == 1
    found = {d["code"] for ds in payload["files"].values() for d in ds}
    assert "B002" in found


# ---------------------------------------------------------------------------
# N-codes — JEPSEN_TPU_* knob threading
# ---------------------------------------------------------------------------

from jepsen_tpu.analyze.suites import (  # noqa: E402
    lint_knobs,
    lint_metrics,
    registered_metrics,
)


def _knob_pkg(tmp_path, src):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(src)
    return pkg


def _all(diags_by_file):
    return [d for ds in diags_by_file.values() for d in ds]


def test_n001_toggle_without_cli_flag(tmp_path):
    pkg = _knob_pkg(tmp_path, (
        "import os\n"
        "def foo_enabled():\n"
        "    return os.environ.get('JEPSEN_TPU_FOO', '') != '0'\n"))
    out = _all(lint_knobs(pkg, cli_text="",
                          docs_text="JEPSEN_TPU_FOO"))
    assert {d.code for d in out} == {"N001"}
    # a cli.py mention clears it
    out = _all(lint_knobs(pkg, cli_text="JEPSEN_TPU_FOO",
                          docs_text="JEPSEN_TPU_FOO"))
    assert out == []


def test_n001_needs_the_enabled_idiom(tmp_path):
    # a plain function read is not a toggle: no N001
    pkg = _knob_pkg(tmp_path, (
        "import os\n"
        "def depth():\n"
        "    return int(os.environ.get('JEPSEN_TPU_DEPTH', '4'))\n"))
    out = _all(lint_knobs(pkg, cli_text="",
                          docs_text="JEPSEN_TPU_DEPTH"))
    assert out == []


def test_n002_import_time_read_of_cli_claimed_knob(tmp_path):
    pkg = _knob_pkg(tmp_path, (
        "import os\n"
        "MODE = os.environ.get('JEPSEN_TPU_BAR', 'auto')\n"))
    out = _all(lint_knobs(pkg, cli_text="JEPSEN_TPU_BAR",
                          docs_text="JEPSEN_TPU_BAR"))
    assert {d.code for d in out} == {"N002"}
    # env-only tuning constants (no cli.py claim) are exempt
    out = _all(lint_knobs(pkg, cli_text="",
                          docs_text="JEPSEN_TPU_BAR"))
    assert out == []


def test_n003_undocumented_knob_and_internal_exemption(tmp_path):
    pkg = _knob_pkg(tmp_path, (
        "import os\n"
        "def f():\n"
        "    a = os.environ['JEPSEN_TPU_MYSTERY']\n"
        "    b = os.environ.get('JEPSEN_TPU_PROC_ID')\n"
        "    return a, b\n"))
    out = _all(lint_knobs(pkg, cli_text="", docs_text=""))
    assert [(d.code, d.severity) for d in out] == [("N003", "warning")]
    assert "JEPSEN_TPU_MYSTERY" in out[0].message


def test_n003_membership_test_counts_as_read(tmp_path):
    pkg = _knob_pkg(tmp_path, (
        "import os\n"
        "def f():\n"
        "    return 'JEPSEN_TPU_GHOST' in os.environ\n"))
    out = _all(lint_knobs(pkg, cli_text="", docs_text=""))
    assert {d.code for d in out} == {"N003"}


def test_knoblint_suppression(tmp_path):
    pkg = _knob_pkg(tmp_path, (
        "import os\n"
        "def foo_enabled():\n"
        "    return os.environ.get('JEPSEN_TPU_FOO') == '1'"
        "  # knoblint: ok\n"))
    out = _all(lint_knobs(pkg, cli_text="", docs_text=""))
    assert out == []


def test_package_knobs_are_threaded():
    """The CI gate: every knob the package reads has its CLI flag, no
    cli-claimed knob freezes at import, everything is documented."""
    out = _all(lint_knobs())
    assert [str(d) for d in out if d.severity == "error"] == []
    assert [str(d) for d in out if d.severity == "warning"] == []


# ---------------------------------------------------------------------------
# O-codes — jtpu_* metrics contract
# ---------------------------------------------------------------------------

def _metrics_pkg(tmp_path, *names):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    body = "from x import REGISTRY\n" + "".join(
        f"M{i} = REGISTRY.counter('{n}', 'h')\n"
        for i, n in enumerate(names))
    (pkg / "m.py").write_text(body)
    return pkg


def test_o001_consumer_references_unregistered_series(tmp_path):
    pkg = _metrics_pkg(tmp_path, "jtpu_real_total")
    web = tmp_path / "web.py"
    web.write_text("PANEL = ['jtpu_real_total', 'jtpu_ghost_total']\n")
    out = _all(lint_metrics(pkg, consumers=[web]))
    o001 = [d for d in out if d.code == "O001"]
    assert len(o001) == 1 and "jtpu_ghost_total" in o001[0].message


def test_o001_histogram_suffixes_resolve_to_family(tmp_path):
    pkg = _metrics_pkg(tmp_path, "jtpu_lat_seconds")
    web = tmp_path / "web.py"
    web.write_text("Q = 'jtpu_lat_seconds_bucket'\n")
    out = [d for d in _all(lint_metrics(pkg, consumers=[web]))
           if d.code == "O001"]
    assert out == []


def test_o002_orphans_aggregate_into_one_warning(tmp_path):
    pkg = _metrics_pkg(tmp_path, "jtpu_used_total",
                       "jtpu_orphan_a_total", "jtpu_orphan_b_total")
    web = tmp_path / "web.py"
    web.write_text("P = 'jtpu_used_total'\n")
    out = _all(lint_metrics(pkg, consumers=[web]))
    o002 = [d for d in out if d.code == "O002"]
    assert len(o002) == 1 and o002[0].severity == "warning"
    assert "jtpu_orphan_a_total" in o002[0].message
    assert "jtpu_orphan_b_total" in o002[0].message


def test_metriclint_suppression(tmp_path):
    pkg = _metrics_pkg(tmp_path, "jtpu_real_total")
    web = tmp_path / "web.py"
    web.write_text("G = 'jtpu_ghost_total'  # metriclint: ok\n")
    out = [d for d in _all(lint_metrics(pkg, consumers=[web]))
           if d.code == "O001"]
    assert out == []


def test_package_metrics_contract_holds():
    """The CI gate: every series a consumer surface references is
    registered (O001 clean); the mc layer's own series are present."""
    out = _all(lint_metrics())
    assert [str(d) for d in out if d.severity == "error"] == []
    reg = registered_metrics()
    for name in ("jtpu_mc_states_total", "jtpu_mc_schedules_total",
                 "jtpu_mc_violations_total", "jtpu_mc_prune_ratio"):
        assert name in reg, name


def test_benchmark_readers_name_registered_series():
    """O001 over the benchmark's readers: every ``jtpu_*`` series a
    file under ``benchmark/`` names is registered in the package, and
    each such file is one of the default consumer surfaces."""
    import re
    from pathlib import Path

    from jepsen_tpu.analyze.suites import METRIC_CONSUMERS

    named: dict = {}
    for f in sorted((Path(REPO) / "benchmark").rglob("*.py")):
        for m in re.finditer(r"\bjtpu_[a-z0-9_]+\b", f.read_text()):
            named.setdefault(f, set()).add(m.group(0))
    # level_us divides by the search-level counter
    assert any("jtpu_search_levels_total" in v for v in named.values())
    surfaces = [Path(REPO) / c for c in METRIC_CONSUMERS]
    for f in named:
        assert any(f == s or s in f.parents for s in surfaces), f
    out = _all(lint_metrics(consumers=sorted(named)))
    assert [str(d) for d in out if d.code == "O001"] == []


def test_new_codes_registered_and_cli_runs(capsys):
    for code in ("N001", "N002", "N003", "O001", "O002"):
        assert code in SUITE_CODES
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_suites_cli", os.path.join(REPO, "tools",
                                        "lint_suites.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["--knobs", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["errors"] == 0


# ---------------------------------------------------------------------------
# R-codes — retry idempotency
# ---------------------------------------------------------------------------

from jepsen_tpu.analyze.suites import (  # noqa: E402
    lint_retry,
    lint_retry_source,
)


def test_r001_backoff_run_of_mutation_without_info():
    src = (
        "class AClient(Client):\n"
        "    def invoke(self, test, op):\n"
        "        self.backoff.run(lambda: self.conn.put(op.value))\n"
        "        return replace(op, type='ok')\n")
    assert codes(lint_retry_source(src, "fix.py"),
                 "error") == {"R001"}
    # same construct with an :info completion path is the idiom — clean
    ok = src + (
        "\n"
        "    def invoke2(self, test, op):\n"
        "        try:\n"
        "            self.backoff.run(lambda: self.conn.put(op.value))\n"
        "            return replace(op, type='ok')\n"
        "        except Exception:\n"
        "            return replace(op, type='info')\n")
    diags = lint_retry_source(ok, "fix.py")
    assert [d for d in diags if "invoke2" in d.message] == []


def test_r001_with_conn_of_mutation():
    src = (
        "def invoke(self, test, op):\n"
        "    self.wrapper.with_conn(lambda c: c.write(op.value))\n"
        "    return replace(op, type='ok')\n")
    assert codes(lint_retry_source(src, "fix.py"),
                 "error") == {"R001"}
    # reads through the same wrapper are idempotent — clean
    read = src.replace("c.write", "c.read")
    assert lint_retry_source(read, "fix.py") == []


def test_r001_attempt_loop_mutation_and_r002_swallow():
    src = (
        "def do(conn, op):\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            conn.enqueue(op)\n"
        "            return 'ok'\n"
        "        except Exception:\n"
        "            continue\n")
    got = codes(lint_retry_source(src, "fix.py"), "error")
    assert got == {"R001", "R002"}


def test_r002_only_when_loop_is_retry_shaped():
    # a per-item scan skipping bad items is NOT a retry loop
    scan = (
        "def sweep(files):\n"
        "    for f in files:\n"
        "        try:\n"
        "            load(f)\n"
        "        except Exception:\n"
        "            continue\n")
    assert lint_retry_source(scan, "fix.py") == []
    # kept-last-error used after the loop is the legitimate exit
    kept = (
        "def do(conn, op):\n"
        "    last = None\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            return conn.read(op)\n"
        "        except Exception as e:\n"
        "            last = e\n"
        "    return replace(op, type='fail', error=str(last))\n")
    assert lint_retry_source(kept, "fix.py") == []
    # re-raise after the loop is Backoff.run semantics — clean
    rr = (
        "def do(conn, op):\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            return conn.read(op)\n"
        "        except Exception:\n"
        "            continue\n"
        "    raise RuntimeError('budget')\n")
    assert lint_retry_source(rr, "fix.py") == []


def test_r_probe_loops_and_backoff_run_itself_are_clean():
    probe = (
        "def wait(self):\n"
        "    while not self.bo.exhausted():\n"
        "        try:\n"
        "            self.health_check()\n"
        "            return\n"
        "        except Exception:\n"
        "            sleep(self.bo.step())\n"
        "    raise RuntimeError('dead')\n")
    assert lint_retry_source(probe, "fix.py") == []


def test_retrylint_suppression():
    src = (
        "def do(conn, op):\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            conn.enqueue(op)  # server dedups; retrylint: ok\n"
        "            return 'ok'\n"
        "        except Exception:\n"
        "            continue\n"
        "    raise RuntimeError('budget')\n")
    assert lint_retry_source(src, "fix.py") == []


def test_package_retry_discipline_holds():
    """The CI gate: no automatically retried mutation in the package
    without :info handling (the reconnect layer, health probes, and
    queue clients must all classify clean)."""
    out = _all(lint_retry())
    assert [str(d) for d in out
            if d.severity == "error"] == []


def test_lint_suites_cli_retry_flag(capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lint_suites_cli_r", os.path.join(REPO, "tools",
                                          "lint_suites.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["--retry", "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["errors"] == 0
