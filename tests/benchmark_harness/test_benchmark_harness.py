"""CPU tests of the benchmark harness (`benchmark/`): generator,
reference, control, planted faults, trace reduction, lookup by name (a
model, a configuration, a mix and a cell added as files), and the
refusal to run without a chip.

    JAX_PLATFORMS=cpu python3 -m pytest tests/benchmark_harness -q
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import shutil
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import run  # noqa: E402
from core import gen, readers, reference, spec, sut, xtrace  # noqa: E402

SEED = 3_000_000_019   # more than 32 signed bits hold
#: a size a test run can hold: pools of 2 tests of 12 keys, or of 2
#: 120-op histories of the middle fifth of such histories' work
SMALL = {"etcdemo-stale": ({"keys_per_test": 12}, {"pool": 2}),
         # 20 keys: at 12, one seed below draws no key whose crashed op
         # took effect and was seen, which the control needs
         "etcdemo-clean": ({"keys_per_test": 20}, {"pool": 2}),
         "zk1k-stale": ({"encoded_ops": 120},
                        {"pool": 2, "work": [2900, 3600]})}
#: the model of every cell above
CAS = spec.model({"checker": {"model": "cas_register"}})


@pytest.fixture(autouse=True)
def _leave_the_process_as_found():
    """A run sets the compile cache's directory and switches the
    program's spans; other test files share this process."""
    import jax

    from jepsen_tpu.obs import trace

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    conf = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    if env is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = env
    for k, v in conf.items():
        jax.config.update(k, v)
    trace.enable(None)
    trace.recorder(None).clear()


def small_run(cell, *, seed=SEED, sut=None, trace=0, control=0):
    return run.run(["--workload", cell, "--seed", str(seed),
                    "--seconds", "1", "--trace", str(trace),
                    "--control", str(control)],
                   require_chip=False, config_override=SMALL[cell][0],
                   traffic_override=SMALL[cell][1], sut=sut)


def cell_parts(cell):
    _wl, config, traffic = spec.cell(spec.load(), cell)
    return {**config, **SMALL[cell][0]}, {**traffic, **SMALL[cell][1]}


# --- generator -------------------------------------------------------------


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_generator_is_deterministic_per_seed(cell):
    config, traffic = cell_parts(cell)
    a = gen.make_pool(SEED, config, traffic, CAS)
    assert a == gen.make_pool(SEED, config, traffic, CAS)
    assert a != gen.make_pool(SEED + 1, config, traffic, CAS)


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_every_seed_gets_the_same_sizes(cell):
    config, traffic = cell_parts(cell)
    for seed in (1, SEED, 2 ** 40 + 7):
        for events, truth in gen.make_pool(seed, config, traffic, CAS):
            if config["layout"] == "single":
                assert gen.encoded_count(events) == config["encoded_ops"]
                rows = reference.rows_of(events)
                assert sum(op["type"] == "info" for op in rows) \
                    == traffic["crashes"]
                lo, hi = traffic["work"]
                assert lo <= reference.check(CAS, rows, config["init"])[1] \
                    <= hi
            else:
                assert len(truth) == config["keys_per_test"]
                assert sum(not v for v in truth.values()) == round(
                    traffic["stale_share"] * config["keys_per_test"])


def test_stale_reads_are_invalid_and_were_written():
    config, traffic = cell_parts("etcdemo-stale")
    (events, truth), = gen.make_pool(SEED, config, {**traffic, "pool": 1},
                                     CAS)
    for k, e in reference.split_keys(events).items():
        rows = reference.rows_of(e)
        assert reference.check(CAS, rows, None)[0] is truth[k]
        if not truth[k]:
            # the control (stale reads allowed) accepts every stale key
            assert reference.check(CAS, rows, None, control=True)[0] is True


# --- reference -------------------------------------------------------------


def _program_answer(events, init):
    """The host engines' verdict, and the deepest level of the host
    DFS's search (with the static prepass off: it refutes some of these
    histories with no search, at depth 0)."""
    sys.path.insert(0, ROOT)
    from jepsen_tpu.checker.linear import check_opseq_linear
    from jepsen_tpu.checker.seq import check_opseq
    from jepsen_tpu.history import Op, encode_ops
    from jepsen_tpu.models import cas_register

    model = cas_register() if init is None else cas_register(init)
    seq = encode_ops([Op(*e) for e in events], model.f_codes)
    return (check_opseq_linear(seq, model, lint=False)["valid"],
            check_opseq(seq, model, lint=False, hb=False)["max_depth"])


@pytest.mark.parametrize("init", [None, 0])
def test_reference_agrees_with_host_linear_on_tiny_histories(init):
    rng = random.Random(SEED)
    seen = set()
    for _ in range(150):
        h = CAS.register_history(rng, n_ops=rng.randrange(4, 14),
                                 n_procs=3, overlap=3, crash_p=0.1,
                                 max_crashes=2, n_values=3, init=init)
        if rng.random() < 0.5:
            # a read of a random value: valid or not, either may come
            reads = [i for i, e in enumerate(h)
                     if e[1] == "ok" and e[2] == "read"]
            if reads:
                i = rng.choice(reads)
                h[i] = h[i][:3] + (rng.randrange(3),)
        want, want_depth = _program_answer(h, init)
        got, _seen, depth = reference.check(CAS, reference.rows_of(h), init)
        assert got is want
        if not got:
            assert depth == want_depth
        seen.add(got)
    assert seen == {True, False}


def test_replay_refuses_a_broken_order():
    h = [(0, "invoke", "write", 1), (0, "ok", "write", 1),
         (1, "invoke", "read", None), (1, "ok", "read", 1)]
    rows = reference.rows_of(h)
    assert reference.replay(CAS, rows, [0, 1], None)
    assert not reference.replay(CAS, rows, [1, 0], None)  # read before write
    assert not reference.replay(CAS, rows, [0], None)     # an ok op missing
    assert not reference.replay(CAS, rows, [0, 1, 1], None)
    # partial linearizations, as an invalid verdict's deepest paths
    assert reference.replay(CAS, rows, [0], None, whole=False)
    assert not reference.replay(CAS, rows, [1], None, whole=False)
    h2 = [(0, "invoke", "write", 1), (0, "ok", "write", 1),
          (1, "invoke", "write", 2), (1, "ok", "write", 2)]
    rows2 = reference.rows_of(h2)
    # the second write was invoked after the first returned
    assert not reference.replay(CAS, rows2, [1], None, whole=False)
    assert reference.replay(CAS, rows2, [0, 1], None)


# --- correct: the sound run, the control, the planted faults ---------------


@pytest.mark.parametrize("cell", sorted(SMALL))
def test_a_sound_run_is_correct(cell):
    r = small_run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["metrics"]["verified_ops_per_s"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(SMALL))
@pytest.mark.parametrize("seed", [11, 2 ** 33 + 5, SEED])
def test_the_control_in_the_programs_place_is_not_correct(cell, seed):
    r = small_run(cell, seed=seed, control=1)
    assert not r["correct"]
    assert r["checks"]["verdict_mismatches"]["value"] >= 1


class Planted:
    """The program with a fault planted where its answers are produced:
    `core/sut.py` in every other respect."""

    def __init__(self, fault):
        self.fault = fault

    def build(self, config):
        chk, model = sut.build(config)
        fault = self.fault

        class Faulty:
            def check(self, test, history):
                return fault(chk.check(test, history))

        return Faulty(), model

    def __getattr__(self, name):
        return getattr(sut, name)


def _per_key(res):
    return res["results"] if "results" in res else {None: res}


def _flip_one(res):
    # an answer altered where it is produced
    r = next(iter(_per_key(res).values()))
    r["valid"] = not r["valid"]
    return res


def _drop_half(res):
    # half of the batch left out
    keys = list(res["results"])
    return {**res, "results": {k: res["results"][k]
                               for k in keys[:len(keys) // 2]}}


def _unchanged(res):
    # the search leaves its starting state: every key still "valid",
    # with no linearization, as no search found one
    for r in _per_key(res).values():
        r.update(valid=True, linearization=None)
    return res


def _always_invalid(res):
    # "invalid" without the work: no level searched, no path
    for r in _per_key(res).values():
        r.update(valid=False, max_depth=0, final_paths=[])
    return res


def _stops_early(res):
    # the sweep of an invalid history given up halfway, its verdict
    # kept: the deepest paths are those of the levels searched
    for r in _per_key(res).values():
        if r["valid"] is False:
            r["max_depth"] //= 2
            for p in r["final_paths"]:
                del p["linearized"][r["max_depth"]:]
    return res


def _bad_certificate(res):
    # a linearization altered where it is produced: its last two ops
    # swapped, the verdict left as it was
    for r in _per_key(res).values():
        lin = r.get("linearization")
        if r["valid"] is True and lin is not None and len(lin) > 1:
            r["linearization"] = lin[:-2] + [lin[-1], lin[-2]]
    return res


#: (cell, fault, the number that has to catch it).  `etcdemo-clean` has
#: no invalid key: a search stopped early answers its truth, so
#: `_stops_early` cannot show there (`etcdemo-stale` runs the same code
#: and catches it); a search left at its start answers its truth too,
#: but with no linearization on any key
FAULTS = [(c, f, n) for c in ("etcdemo-stale", "zk1k-stale")
          for f, n in ((_flip_one, "verdict_mismatches"),
                       (_unchanged, "verdict_mismatches"),
                       (_always_invalid, "shallow_refutations"),
                       (_stops_early, "shallow_refutations"))] \
    + [("etcdemo-stale", _drop_half, "verdict_mismatches"),
       ("etcdemo-stale", _bad_certificate, "bad_certificates")] \
    + [("etcdemo-clean", f, n) for f, n in (
        (_flip_one, "verdict_mismatches"),
        (_unchanged, "uncertified_valid_pct"),
        (_always_invalid, "verdict_mismatches"),
        (_drop_half, "verdict_mismatches"),
        (_bad_certificate, "bad_certificates"))]


@pytest.mark.parametrize("cell,fault,number", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f, _n in FAULTS])
def test_a_planted_fault_is_not_correct(cell, fault, number):
    r = small_run(cell, sut=Planted(fault))
    assert not r["correct"], r["checks"]
    assert r["checks"][number]["value"] >= 1, r["checks"]


# --- the register cells read as before their model had a file of its own --


#: (cell, seed) -> sha256 (first 16 hex digits) of ``repr`` of the pool,
#: and of the reference's answer for every key (valid, configurations
#: visited, depth) with its roofline bytes (`readers.config_bytes`,
#: `table_bytes`), at the sizes of SMALL: as the harness read them when
#: the CAS register was written into `core/gen.py` and `reference.py`
GOLDEN = {
    ("etcdemo-stale", 11): ("ff66bc3876834abb", "7c4ca3d6ebf3460c"),
    ("etcdemo-stale", SEED): ("54b4245f4cb6a435", "ccd1909e46d3c9db"),
    ("etcdemo-stale", 2 ** 40 + 7): ("d8ad281420e3f9f8", "a72abc9d7005186c"),
    ("zk1k-stale", 11): ("56c418464962fd91", "cc1bc5922a1c0c1e"),
    ("zk1k-stale", SEED): ("70ac671cdca24f68", "c867c1b2d9cc0335"),
    ("zk1k-stale", 2 ** 40 + 7): ("873ed6e5bb4b5ab3", "29531fe0f5b4e2fe"),
}


def _digest(x):
    return hashlib.sha256(repr(x).encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell,seed", sorted(GOLDEN))
def test_the_register_cells_read_as_before(cell, seed):
    config, traffic = cell_parts(cell)
    model = spec.model(config)
    pool = gen.make_pool(seed, config, traffic, model)
    bits = model.state_bits(config)
    answers = []
    for events, _truth in pool:
        per = reference.split_keys(events) \
            if config["layout"] == "independent" else {None: events}
        for k, e in per.items():
            rows = reference.rows_of(e)
            answers.append((k,) + reference.check(model, rows, config["init"])
                           + (readers.config_bytes(rows, bits),
                              readers.table_bytes(rows, bits)))
    assert (_digest(pool), _digest(answers)) == GOLDEN[cell, seed]


# --- trace reduction -------------------------------------------------------


def _ev(t0, dur, name="fusion", line=xtrace.OPS_LINE,
        plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name, "t0": t0,
            "dur": dur}


def test_union_and_busy_by_hand():
    assert xtrace.union([(0, 2), (1, 3), (5, 6)]) == 4
    assert xtrace.union([]) == 0
    # on plane 0, "a" holds "b" (a while loop and its body), then "a"
    ev = [_ev(0.0, 2.0, "%a = s32[] while(...)"), _ev(0.5, 1.0, "b"),
          _ev(3.0, 1.0, "a"), _ev(0.0, 2.0, "a", plane="/device:TPU:1"),
          _ev(0.0, 9.0, "m", line=readers.MODULES_LINE)]
    # plane 0: [0, 2) and [3, 4) -> 3; plane 1: 2; mean 2.5
    assert xtrace.busy_s(ev, 0.0, 10.0) == pytest.approx(2.5)
    # self time: "a" 1 + 1 + 2 (plane 1), "b" 1
    assert xtrace.top_ops(ev, 0.0, 10.0) == [["a", 4.0], ["b", 1.0]]
    gaps = xtrace.idle_gaps(ev, 0.0, 10.0,
                            [{"name": "host", "t0": 4.0, "t1": 10.0}])
    assert gaps[0] == ["host", pytest.approx(6.0)]
    assert gaps[1] == ["none", pytest.approx(1.0)]
    ctx = {"window": (0.0, 10.0), "events": ev}
    assert readers.module_seconds(ctx, "^m$") == pytest.approx(9.0)


def test_align_uses_the_sync_annotation():
    ev = [_ev(100.0, 0.0, xtrace.SYNC, line="python3", plane="/host:CPU"),
          _ev(101.0, 1.0)]
    out = xtrace.align(ev, 5.0)
    assert out[1]["t0"] == pytest.approx(6.0)


def test_reduction_of_a_trace_recorded_on_the_chip():
    path = os.path.join(HERE, "data", "trace_sample.json")
    with open(path) as f:
        sample = json.load(f)
    ev = sample["events"]
    t0 = min(e["t0"] for e in ev)
    t1 = max(e["t0"] + e["dur"] for e in ev)
    busy = xtrace.busy_s(ev, t0, t1)
    assert busy == pytest.approx(sample["busy_s"], rel=1e-9)
    assert 0 < busy <= t1 - t0
    top = xtrace.top_ops(ev, t0, t1)
    assert [n for n, _ in top] == sample["top_ops"]
    ctx = {"window": (t0, t1), "events": ev}
    assert readers.module_seconds(ctx, readers.LEVEL_KERNELS) > 0


# --- the kernel roofline's byte count --------------------------------------


def test_roofline_bytes_by_hand():
    # two ok writes open together, one crashed write: width 2, 1 crash
    h = [(0, "invoke", "write", 1), (1, "invoke", "write", 2),
         (0, "ok", "write", 1), (1, "ok", "write", 2),
         (2, "invoke", "write", 3)]
    rows = reference.rows_of(h)
    # the register's value, from the model's file: ceil(log2(5 + 1)) = 3
    bits = CAS.state_bits({"values": 5})
    assert bits == 3
    # position: ceil(log2(3 + 1)) = 2 bits; window 2; crashes 1;
    # value 3 bits -> 8 bits = 1 byte
    assert readers.config_bytes(rows, bits) == 1
    # per op: f 2 + values 2 * 3 + event positions 2 * ceil(log2(7)) = 14
    # bits; 3 ops = 42 bits -> 6 bytes
    assert readers.table_bytes(rows, bits) == 6


# --- found by name ---------------------------------------------------------


def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    root = tmp_path
    bench = root / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__"))
    sp = spec.load()
    sp["configs"].append({"name": "dummy-reg", "source": "x",
                          "file": "benchmark/configs/dummy-reg.json",
                          "reduced": [], "why": "x"})
    sp["workloads"].append({"name": "dummy", "config": "dummy-reg",
                            "traffic": "dummy-mix", "chips": 1,
                            "why": "x"})
    sp["per_layer"].append({"name": "dummy_metric", "unit": "count",
                            "better": "lower", "source": "program_counter",
                            "layer": "compile", "moves": "setup_s",
                            "workloads": ["dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(sp))
    config = json.loads((bench / "configs" / "zookeeper-register.json")
                        .read_text())
    (bench / "configs" / "dummy-reg.json").write_text(
        json.dumps({**config, "encoded_ops": 30}))
    (bench / "traffic" / "dummy-mix.json").write_text(
        json.dumps({"pool": 1, "overlap": 3, "crashes": 0}))
    (bench / "metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return 7\n")
    sp2 = spec.load(str(root))
    wl, cfg, traffic = spec.cell(sp2, "dummy", str(root))
    assert cfg["encoded_ops"] == 30 and traffic["overlap"] == 3
    ms = spec.metrics(sp2, "dummy", traced=True)
    assert [m["name"] for m in ms] == ["dummy_metric"]
    assert spec.reader(ms[0], str(root))({}) == 7
    assert [m["name"] for m in spec.metrics(sp2, "dummy", traced=False)] \
        == ["verified_ops_per_s", "setup_s"]
    (events, _truth), = gen.make_pool(SEED, cfg, traffic,
                                      spec.model(cfg, str(root)))
    assert gen.encoded_count(events) == 30


#: a read/write register (knossos's register) as a model file of its own
TOY_MODEL = textwrap.dedent('''
    """A read/write register: a model added as files."""
    import math

    TRAFFIC_KEYS = {"stale_at"}


    def history(rng, p, n_ops, exact):
        # one op at a time, valid by construction
        state, h = p["init"], []
        for i in range(n_ops):
            w = i % p["procs"]
            if rng.random() < 0.5:
                h += [(w, "invoke", "read", None), (w, "ok", "read", state)]
            else:
                state = rng.randrange(p["values"])
                h += [(w, "invoke", "write", state),
                      (w, "ok", "write", state)]
        return h


    def corrupt(events, p):
        # the first read from stale_at on returns a value written before
        # and overwritten since: no two ops overlap, so no order allows it
        written = set()
        for i, (w, typ, f, v) in enumerate(events):
            if typ == "ok" and f == "write":
                written.add(v)
            elif typ == "ok" and i >= p["stale_at"] * len(events) \\
                    and written - {v}:
                out = list(events)
                out[i] = (w, typ, f, min(written - {v}))
                return out
        return None


    def start(init, control):
        return (init, frozenset([init]) if control else frozenset())


    def step(state, op, control):
        # control: a read may return any value the register has held
        val, held = state
        if op["f"] == "read":
            ok = op["value"] == val or (control and op["value"] in held)
            return state if ok else None
        new = op["value"]
        return (new, held | {new}) if control else (new, held)


    def state_bits(config):
        return math.ceil(math.log2(config["values"]))
''')


def _toy_checkout(root, model="register"):
    """A copy of the benchmark with a model, a configuration, a mix and a
    cell added as files and entries, and nothing copied edited."""
    bench = root / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__"))
    (bench / "models" / "register.py").write_text(TOY_MODEL)
    (bench / "configs" / "toy-register.json").write_text(json.dumps({
        "name": "toy-register", "layout": "single",
        "checker": {"model": model, "algorithm": "device"},
        "procs": 3, "encoded_ops": 24, "values": 4, "init": 0,
        "test_seconds": 20, "nemesis": {"name": "none", "period_s": 10}}))
    (bench / "traffic" / "toy-stale.json").write_text(json.dumps({
        "pool": 2, "stale_share": 1, "stale_at": 0.5}))
    sp = spec.load()
    sp["configs"].append({"name": "toy-register", "source": "x",
                          "file": "benchmark/configs/toy-register.json",
                          "reduced": [], "why": "x"})
    sp["workloads"].append({"name": "toy", "config": "toy-register",
                            "traffic": "toy-stale", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(sp))
    return str(root)


def _toy_run(root, control):
    return run.run(["--workload", "toy", "--seed", str(SEED), "--seconds",
                    "1", "--trace", "0", "--control", str(control)],
                   require_chip=False, root=root)


@pytest.mark.parametrize("control", [0, 1])
def test_a_model_added_as_files_runs_end_to_end(tmp_path, control):
    r = _toy_run(_toy_checkout(tmp_path), control)
    if control:
        # a read of an overwritten value passes the control
        assert not r["correct"]
        assert r["checks"]["verdict_mismatches"]["value"] >= 1
    else:
        assert r["correct"], r["checks"]
        assert r["attempted"] >= 1 and r["failed"] == 0
        assert r["metrics"]["verified_ops_per_s"]["value"] > 0


def test_a_model_with_no_file_is_an_error(tmp_path):
    root = _toy_checkout(tmp_path, model="no_such_model")
    with pytest.raises(KeyError, match="no_such_model.py"):
        _toy_run(root, 0)
    with pytest.raises(KeyError, match="model"):
        spec.model({"layout": "single", "checker": {"algorithm": "device"}})


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("cpu")


# --- no chip ---------------------------------------------------------------


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "zk1k-stale",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr
